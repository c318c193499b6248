"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]       # one chip
    python chip_smoke.py --chips 4        # 2x2 mesh, expert-parallel step

One chip: granite-moe-1b-a400m at its published widths (24 layers,
d_model 1024, 16 query / 8 KV heads of 64, 32 experts top-8, d_ff 512,
vocab 49155) with random weights from ``--seed``:

1. upcycle — init the dense parent and run ``upcycle_params`` into the
   32-expert MoE;
2. train — 3 ``Trainer`` steps on synthetic data (batch 2 x seq 512);
   the first loss must agree with an ``implementation="xla"`` loss;
3. serve — 4 staggered greedy requests of 16 new tokens through the
   paged, chunked ``ServeEngine``; all must complete, and the engine's
   step logits (one prefill chunk plus decode steps) must agree with
   ``zoo.forward_train`` on the ``ref`` kernels at highest precision.

``--chips 4`` runs only the sharded path: the upcycled train step on a
``("data", "model")`` 2x2 mesh with sorted dispatch and ``ep="a2a"``,
against the same step on one device.

Everything runs in this one process (a second process could not reach
the chip). The script exits non-zero, printing no result, when JAX finds
no TPU, when the kernels do not resolve to Pallas, or when any check
fails. On success the last stdout line is one JSON object naming the
device. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ArchConfig, get_config, get_reduced  # noqa: E402
from repro.core.upcycle import upcycle_params  # noqa: E402
from repro.data import make_iterator  # noqa: E402
from repro.models import model_zoo as zoo  # noqa: E402
from repro.models import param as pm  # noqa: E402
from repro.obs import MemorySink, Tracker  # noqa: E402
from repro.optim import adafactor, constant  # noqa: E402
from repro.serve import Request, ServeConfig, ServeEngine  # noqa: E402
from repro.training import TrainConfig, Trainer  # noqa: E402

ARCH = "granite-moe-1b-a400m"

# First-step loss, Pallas kernels vs the XLA path on the same chip. At
# the TPU's default precision every matmul of either path rounds its
# operands to bf16 (relative 2^-9 = 2e-3), in different places and
# orders; the loss averages cross-entropy over 1024 tokens, so the two
# agree far inside 1e-2 absolute on a loss of about ln(49155) = 10.8.
LOSS_ATOL = 1e-2
# Serve step logits vs the float32 reference, both at highest precision,
# as the largest |difference| over the largest |reference logit|. They
# differ only in summation order (paged blocks, online softmax, the
# kernel's ragged walk vs the one-hot oracle), so by float32 roundoff:
# a relative 2^-24 = 6e-8 per operation, grown to about 1e-6 over 24
# layers. 1e-3 stays far below what a cache layout, masking or routing
# error gives (a wrong block or position moves logits by tens of
# percent). The same check at the TPU's default precision (bf16
# operands, 2^-9 relative) is printed but not held to a limit: its size
# is what the next bf16 work measures.
LOGITS_RTOL = 1e-3
# Sharded expert-parallel step vs one device: the same arithmetic with
# sums reduced in another order across chips and the grouped GEMM fed
# rows in all-to-all order; both at the same (bf16-operand) precision.
EP_LOSS_RTOL = 2e-3


def granite(*, reduced: bool = False) -> ArchConfig:
    """granite-moe-1b-a400m routed dropless, as published: the released
    model has no expert capacity limit. ``capacity_factor`` equal to the
    expert count lets every expert take its whole routing group
    (``routing.capacity`` caps at the group), so no assignment is
    dropped and a token's output does not depend on which other tokens
    share its batch — which the serve-vs-reference check relies on."""
    cfg = get_reduced(ARCH) if reduced else get_config(ARCH)
    moe = dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)
    )
    return dataclasses.replace(cfg, moe=moe)


def upcycle(cfg: ArchConfig, seed: int):
    """Random dense parent from ``seed`` -> upcycled MoE value tree."""
    dense_cfg = cfg.dense_parent()

    def build(key):
        k_dense, k_moe = jax.random.split(key)
        dense = zoo.init_params(k_dense, dense_cfg)
        return pm.split(upcycle_params(dense, dense_cfg, cfg, k_moe))[0]

    return jax.jit(build)(jax.random.PRNGKey(seed))


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train(cfg, params, *, impl: str, steps: int, batch: int, seq: int,
          remat: str, log=print):
    """``steps`` Trainer steps from ``params``; returns (trained params,
    report). The first loss is checked against the XLA path's loss of
    the same parameters on the same batch."""
    ac = zoo.ApplyCfg(remat=remat, moe_impl=impl, attn_impl=impl).resolve()
    xla_ac = dataclasses.replace(ac, moe_impl="xla", attn_impl="xla")
    first_batch = next(make_iterator(cfg, global_batch=batch, seq_len=seq))
    loss_xla = float(jax.jit(
        lambda p, b: zoo.loss_fn(p, b, cfg, ac=xla_ac)[0]
    )(params, first_batch))

    sink = MemorySink()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        tr = Trainer(
            cfg, adafactor(constant(1e-3)),
            make_iterator(cfg, global_batch=batch, seq_len=seq), ckpt_dir,
            ac=ac, tc=TrainConfig(log_every=steps), tracker=Tracker((sink,)),
            log_fn=log,
        )
        out = tr.run(steps, init_params=params)
        params = out["state"]["params"]
        del out, tr  # the optimizer state goes with them
    wall = time.perf_counter() - t0
    rows = [r for r in sink.rows if r["kind"] == "train"]
    losses = [r["loss"] for r in rows]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise RuntimeError(f"train: expected {steps} finite losses, got "
                           f"{losses}")
    diff = abs(losses[0] - loss_xla)
    report = {
        "losses": losses, "loss_xla": loss_xla, "loss_diff": diff,
        "step_ms": [r["step_ms"] for r in rows], "wall_s": wall,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }
    log(f"[train] kernels moe={ac.moe_impl} attn={ac.attn_impl} "
        f"remat={remat} batch={batch}x{seq}")
    log(f"[train] losses={losses} first-step loss vs xla: "
        f"{losses[0]!r} vs {loss_xla!r}, |diff|={diff!r} "
        f"(tolerance {LOSS_ATOL})")
    log(f"[train] step_ms={report['step_ms']} (step 1 includes compile) "
        f"peak_bytes_in_use={report['peak_bytes_in_use']}")
    if diff > LOSS_ATOL:
        raise RuntimeError(f"train: first-step loss {losses[0]} differs "
                           f"from the XLA path's {loss_xla} by {diff}")
    return params, report


def serve(cfg, params, *, impl: str, seed: int, prompt_lens, max_new: int,
          block_size: int, n_decode_check: int, log=print) -> dict:
    """Staggered greedy requests through the paged chunked engine, then
    the engine step's logits against the reference forward."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    max_len = block_size * -(-(max(prompt_lens) + max_new) // block_size)
    eng = ServeEngine(
        params, cfg,
        ServeConfig(paged=True, max_batch=len(prompts), max_len=max_len,
                    block_size=block_size),
        ac=zoo.ApplyCfg(moe_impl=impl, attn_impl=impl),
    )
    reqs = [Request(rid=i, prompt=p, max_new=max_new, arrival=2 * i)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    outs, stats = eng.serve(reqs)
    wall = time.perf_counter() - t0
    done = [r.rid for r in reqs if stats[r.rid]["status"] == "completed"
            and len(outs[r.rid]) == len(r.prompt) + max_new]
    log(f"[serve] {len(done)}/{len(reqs)} requests completed, "
        f"{max_new} new tokens each, prompts {list(prompt_lens)}, "
        f"block_size={block_size} chunk_size={eng.sc.chunk_size} "
        f"mixed_steps={eng.last_stats['mixed_steps']} "
        f"compile_count={eng.last_stats['compile_count']} "
        f"wall_s={wall!r}")
    if len(done) != len(reqs):
        raise RuntimeError(f"serve: requests not completed: "
                           f"{ {r.rid: stats[r.rid] for r in reqs} }")
    prompt = prompts[0][: eng.sc.chunk_size]
    check = step_logits_vs_reference(
        cfg, params, eng.ac, prompt, n_decode=n_decode_check,
        block_size=block_size, precision="highest",
    )
    default = step_logits_vs_reference(
        cfg, params, eng.ac, prompt, n_decode=n_decode_check,
        block_size=block_size, precision="default",
    )
    log(f"[serve] step logits vs ref forward over 1 prefill + "
        f"{n_decode_check} decode steps, both at highest precision: "
        f"max|diff|={check['max_abs_diff']!r} "
        f"max|ref|={check['max_abs_ref']!r} rel={check['rel_diff']!r} "
        f"(tolerance {LOGITS_RTOL}); step at default precision: "
        f"rel={default['rel_diff']!r} (no limit)")
    if check["rel_diff"] > LOGITS_RTOL:
        raise RuntimeError(f"serve: logits differ from the reference: "
                           f"{check}")
    return {"completed": len(done), "wall_s": wall, **check,
            "rel_diff_default_precision": default["rel_diff"]}


def step_logits_vs_reference(cfg, params, ac, prompt, *, n_decode: int,
                             block_size: int, precision: str) -> dict:
    """One chunked prefill plus ``n_decode`` greedy decode steps through
    ``zoo.paged_mixed_step`` (the engine's step function, with the
    engine's resolved ApplyCfg) at matmul ``precision``, compared with
    ``zoo.forward_train`` on the ``ref`` kernels at highest precision
    over the same tokens."""
    S, C = len(prompt), len(prompt)
    nb = -(-(S + n_decode) // block_size)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    none = jnp.zeros((1, nb), jnp.int32)
    step = jax.jit(lambda *a: zoo.paged_mixed_step(*a, cfg, ac=ac))
    cache = zoo.init_paged_serve_cache(cfg, 1 + nb, block_size,
                                       dtype=jnp.float32)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)
    with jax.default_matmul_precision(precision):
        cache, lg = step(params, i32([0]), jnp.asarray([prompt], jnp.int32),
                         cache, none, i32(0), table, i32(0), i32(S))
        rows, tokens = [lg[1]], list(prompt)
        for t in range(n_decode):
            tokens.append(int(jnp.argmax(rows[-1])))
            cache, lg = step(params, i32([tokens[-1]]),
                             jnp.zeros((1, C), jnp.int32), cache, table,
                             i32(S + t), none, i32(0), i32(0))
            rows.append(lg[0])
    got = np.asarray(jnp.stack(rows))

    ref_ac = zoo.ApplyCfg(moe_impl="ref", attn_impl="ref")
    toks = jnp.asarray([tokens], jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(
            lambda p, b: zoo.forward_train(p, b, cfg, ac=ref_ac)
        )(params, {"tokens": toks, "targets": toks})
    want = np.asarray(ref[0, S - 1:S + n_decode])
    max_diff = float(np.abs(got - want).max())
    max_ref = float(np.abs(want).max())
    return {"max_abs_diff": max_diff, "max_abs_ref": max_ref,
            "rel_diff": max_diff / max_ref}


def one_chip(cfg, *, seed: int, impl: str, steps: int, batch: int,
             seq: int, remat: str, prompt_lens, max_new: int,
             block_size: int, n_decode_check: int, log=print,
             phase=lambda name: contextlib.nullcontext()) -> dict:
    """upcycle -> train -> serve; raises on the first failed check.
    ``phase(name)`` wraps each phase (``CompileClock.phase`` times it)."""
    with phase("upcycle"):
        params = upcycle(cfg, seed)
        jax.block_until_ready(params)
    log(f"[upcycle] {cfg.name}: {pm.count_params(params)} parameters, "
        f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}")
    with phase("train"):
        params, tr = train(cfg, params, impl=impl, steps=steps,
                           batch=batch, seq=seq, remat=remat, log=log)
    with phase("serve"):
        sv = serve(cfg, params, impl=impl, seed=seed,
                   prompt_lens=prompt_lens, max_new=max_new,
                   block_size=block_size, n_decode_check=n_decode_check,
                   log=log)
    return {"train": tr, "serve": sv}


def ep_step_vs_one_device(cfg, params, *, mesh, impl: str, batch: int,
                          seq: int, remat: str, log=print) -> dict:
    """The upcycled train step on ``mesh`` with sorted dispatch and
    ``ep="a2a"`` against the same step on one device (no mesh)."""
    from repro.sharding import ShardCtx, tree_shardings
    from repro.training.train_loop import (
        init_train_state, make_train_step, state_axes,
    )

    ndev = mesh.devices.size
    ep = dict(mesh.shape)["model"]
    # One routing group per device (the EP path shards groups over every
    # mesh axis), and a send budget of ep x the balanced share, so the
    # all-to-all drops nothing and both steps compute the same thing.
    moe = dataclasses.replace(
        cfg.moe, ep="a2a", ep_budget_factor=float(ep),
        group_size=batch * seq // ndev,
    )
    cfg = dataclasses.replace(cfg, moe=moe)
    opt = adafactor(constant(1e-3))
    ac = zoo.ApplyCfg(remat=remat, moe_impl=impl, attn_impl=impl)
    data = next(make_iterator(cfg, global_batch=batch, seq_len=seq))
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                             params=params)
    ctx = ShardCtx.for_mesh(mesh)
    # A jitted copy, not device_put: device_put may alias the one-device
    # buffers, which the one-device step then donates.
    state_d = jax.jit(lambda s: s, out_shardings=tree_shardings(
        state_axes(cfg), jax.eval_shape(lambda: state), mesh,
        ctx.param_rules,
    ))(state)
    data_d = jax.device_put(data, tree_shardings(
        {k: "batch seq" for k in data}, data, mesh, ctx.act_rules,
    ))

    one = jax.jit(make_train_step(cfg, opt, ac=ac), donate_argnums=(0,))
    _, m1 = one(state, data)
    m1 = jax.device_get(m1)
    del state
    sharded = jax.jit(make_train_step(cfg, opt, ac=ac, ctx=ctx),
                      donate_argnums=(0,))
    with mesh:
        _, m4 = sharded(state_d, data_d)
    m4 = jax.device_get(m4)
    rel = abs(float(m4["loss"]) - float(m1["loss"])) / abs(float(m1["loss"]))
    peaks = [_peak_bytes(d) for d in mesh.devices.flat]
    log(f"[ep] mesh {dict(mesh.shape)} ep=a2a "
        f"group_size={moe.group_size} batch={batch}x{seq}")
    log(f"[ep] loss one device={float(m1['loss'])!r} "
        f"mesh={float(m4['loss'])!r} rel diff={rel!r} "
        f"(tolerance {EP_LOSS_RTOL}); grad_norm "
        f"{float(m1['grad_norm'])!r} vs {float(m4['grad_norm'])!r}; "
        f"dropped_frac_sum {float(m1['dropped_frac_sum'])!r} vs "
        f"{float(m4['dropped_frac_sum'])!r}")
    log(f"[ep] peak_bytes_in_use per device={peaks}")
    if not np.isfinite(float(m4["loss"])) or rel > EP_LOSS_RTOL:
        raise RuntimeError(f"ep: sharded loss {float(m4['loss'])} vs one "
                           f"device {float(m1['loss'])}")
    return {"loss_one": float(m1["loss"]), "loss_mesh": float(m4["loss"]),
            "rel_diff": rel, "peak_bytes_in_use": peaks}


class CompileClock:
    """Per-phase wall time and compiler seconds, from JAX's monitoring
    events: backend compiles (cold) and persistent-cache loads (warm)."""

    def __init__(self, log=print):
        self.log = log
        self.compile_s = 0.0
        self.cache_load_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        c0, l0, h0 = self.compile_s, self.cache_load_s, self.cache_hits
        yield
        self.log(f"[time] {name}: wall_s={time.perf_counter() - t0!r} "
                 f"compile_s={self.compile_s - c0!r} "
                 f"cache_load_s={self.cache_load_s - l0!r} "
                 f"cache_hits={self.cache_hits - h0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the 2x2-mesh expert-parallel step")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    resolved = zoo.ApplyCfg().resolve()
    print(f"[setup] platform {dev.platform} kind {dev.device_kind!r} "
          f"count {len(devices)} moe={resolved.moe_impl} "
          f"attn={resolved.attn_impl} compile_cache={cache_dir}")
    if (resolved.moe_impl, resolved.attn_impl) != ("pallas", "pallas"):
        print("chip_smoke: kernels did not resolve to pallas",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    cfg = granite()
    if args.chips == 4:
        from repro.launch.mesh import make_debug_mesh

        with clock.phase("upcycle"):
            params = upcycle(cfg, args.seed)
        with clock.phase("ep"):
            ep_step_vs_one_device(
                cfg, params, mesh=make_debug_mesh((2, 2)), impl="auto",
                batch=2, seq=512, remat="full",
            )
    else:
        one_chip(
            cfg, seed=args.seed, impl="auto", steps=3, batch=2, seq=512,
            remat="full", prompt_lens=(23, 45, 9, 31), max_new=16,
            block_size=16, n_decode_check=4, phase=clock.phase,
        )
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
