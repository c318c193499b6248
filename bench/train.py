"""Training cells: the compiled train step with its state, driven from
the seed through its first steps in set-up and on through the window;
the reference follows the first three steps.

The step is the one ``Trainer.run`` builds (``make_train_step`` under
``jax.jit`` with the state donated, the learning-rate scale passed as a
traced scalar) and it is driven the way ``Trainer.run`` drives it: one
host batch per step, one ``device_get`` of the metrics per step. The
benchmark drives it itself because ``Trainer.run`` keeps its state
local, and the check needs the optimizer state after step 1 and the
parameters after step 3.

While the profiler records, each traced step's scalar metrics (those the
step returns, already on the host) are kept as floats: ``run`` returns
them as ``step_metrics``, ``{name: [one value per traced step]}``, for
readers of counters the program adds to its step; an untraced run keeps
none.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import model
import traffic

CHECK_STEPS = 3


EXPERTS = "['experts']"  # expert leaves are (layers, experts, rows, cols)


def _reduce_axes(key: str, ndim: int) -> tuple:
    """All axes of a leaf, but an expert leaf keeps its expert axis: each
    expert's slice counts as a leaf of its own, so a row sent to the wrong
    expert shows even where the experts are exact copies."""
    return tuple(i for i in range(ndim) if not (EXPERTS in key and i == 1))


def leaf_sq(tree) -> dict:
    """Each leaf's sum of squares (per expert for expert leaves)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for p, v in flat:
        k = jax.tree_util.keystr(p)
        out[k] = jnp.square(v.astype(jnp.float32)).sum(
            _reduce_axes(k, v.ndim))
    return out


def norms(sq: dict) -> dict:
    """Host floats ``{leaf: norm}`` from ``leaf_sq``; an expert leaf gives
    one entry per expert, ``<leaf>[e]``."""
    out = {}
    for k, v in jax.device_get(sq).items():
        v = np.sqrt(np.asarray(v, np.float64))
        if v.ndim:
            out.update({f"{k}[{e}]": float(x) for e, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


@jax.jit
def grad_sq_from_adafactor(slots):
    """Each leaf's first gradient, sum of squares, read back from
    Adafactor's state after step 1: with decay 1 - 1^-0.8 = 0 the second
    moments are the gradient's squares themselves, row means when
    factored (the 1e-30 floor they carry is far below float32 resolution
    here)."""
    def one(k, s):
        if "v_row" in s:
            sq, cols = s["v_row"], s["v_col"].shape[-1]
        else:
            sq, cols = s["v"], 1
        return sq.sum(_reduce_axes(k, sq.ndim)) * cols

    flat = jax.tree_util.tree_flatten_with_path(
        slots, is_leaf=lambda x: isinstance(x, dict)
        and ("v" in x or "v_row" in x))[0]
    return {jax.tree_util.keystr(p): one(jax.tree_util.keystr(p), s)
            for p, s in flat}


@jax.jit
def change_sq(p, p0):
    return leaf_sq(jax.tree.map(lambda a, b: a - b, p, p0))


def gap_by_worst_leaf(prog: dict, want: dict, keep=None) -> tuple:
    """max over leaves of |prog - want| / max(want_leaf, median leaf),
    with the leaf it was found on."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    worst, at = 0.0, None
    for k in keys:
        g = abs(prog[k] - want[k]) / max(want[k], med)
        if g > worst or at is None:
            worst, at = g, k
    return worst, at


def moved_leaves(grad_ref: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others (a router's gradient at a copy-upcycled
    start, say) move under Adafactor by round-off alone."""
    med = float(np.median(list(grad_ref.values())))
    return {k for k, v in grad_ref.items() if v >= 1e-3 * med}


class Data:
    """The benchmark's input pipeline: batch ``i`` of the mix, built on
    the host and handed to the device, rows all different."""

    def __init__(self, mix, seed, vocab):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.toks = traffic.ZipfTokens(vocab, mix["tokens"]["zipf_s"])

    def batch(self, i: int, *, device=True):
        b = traffic.train_batch(self.mix, self.seed, i, self.vocab,
                                self.toks)
        return jax.device_put(b) if device else b


def build_step(cfg, mix):
    from repro.models import model_zoo as zoo
    from repro.optim import adafactor, constant
    from repro.training.train_loop import make_train_step

    opt = adafactor(constant(mix["lr"]))
    ac = zoo.ApplyCfg(dispatch="sorted", remat=mix["remat"])
    return opt, jax.jit(make_train_step(cfg, opt, ac=ac),
                        donate_argnums=(0,))


def reference_steps(dims, mix, seed, data, steps=CHECK_STEPS, *,
                    dtype=jnp.float32, batch_fn=None):
    """The family's reference through its first ``steps`` steps from the
    seed's weights: losses, the first gradient's leaf norms, and the leaf
    norms of the parameters' change after the last step."""
    ref = model.family_of(dims).reference
    make_w = model.reference_weights(dims)
    key = model.key_of(seed)
    batch_fn = batch_fn or (lambda i: data.batch(i))
    prec = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(p, b):
        with jax.default_matmul_precision(prec):
            return jax.value_and_grad(
                lambda p: ref.loss(p, b, dims, dtype=dtype), has_aux=True
            )(p)

    update = jax.jit(ref.adafactor_update, static_argnums=(3, 4))
    p = make_w(key)
    st = ref.adafactor_init(p)
    losses, gnorm = [], None
    for i in range(steps):
        (loss, _), g = grad(p, batch_fn(i))
        losses.append(float(loss))
        if i == 0:
            gnorm = norms(jax.jit(leaf_sq)(g))
        p, st = update(p, g, st, mix["lr"], i + 1)
        del g
    del st
    p0 = make_w(key)
    change = norms(change_sq(p, p0))
    return {"losses": losses, "grad": gnorm, "change": change}


def compare(prog: dict, want: dict) -> dict:
    """The three numbers held to limits: the largest first-steps loss
    gap, and the worst leaf's gap of first-gradient norms and of
    parameter-change norms."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              want["losses"]))
    g_gap, g_at = gap_by_worst_leaf(prog["grad"], want["grad"])
    c_gap, c_at = gap_by_worst_leaf(prog["change"], want["change"],
                                    moved_leaves(want["grad"]))
    return {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
            "change_norm_gap": c_gap, "_at": {"grad": g_at, "change": c_at}}


def run(conf, mix, args, clock, t_start, log) -> dict:
    from repro.training.train_loop import init_train_state

    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    seed = args.seed
    make_w = model.program_weights(cfg, dims)
    key = model.key_of(seed)
    opt, step = build_step(cfg, mix)
    state = init_train_state(key, cfg, opt, params=make_w(key))
    data = Data(mix, seed, dims["V"])
    one = jnp.float32(1.0)

    # -- set-up: the first steps, read for the check ---------------------
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        state, mets = step(state, data.batch(i), one)
        prog["losses"].append(float(jax.device_get(mets)["loss"]))
        if i == 0:
            prog["grad"] = norms(grad_sq_from_adafactor(
                state["opt_state"]["slots"]))
    prog["change"] = norms(change_sq(state["params"], make_w(key)))
    setup_s = time.perf_counter() - t_start

    # -- the window ---------------------------------------------------------
    tracing = bool(args.trace)
    ann = (jax.profiler.TraceAnnotation if tracing
           else (lambda name: contextlib.nullcontext()))
    i, n, skipped = CHECK_STEPS, 0, 0
    compiles0 = clock.compiles
    traced, span = [], None
    step_metrics: dict[str, list[float]] = {}
    t0 = time.perf_counter()
    while True:
        el = time.perf_counter() - t0
        if tracing and span is None and not traced \
                and el >= args.seconds / 3:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(args.trace_dir),
                                     profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.traced")
            span.__enter__()
        with ann("bench.data"):
            b = data.batch(i)
        with ann("bench.step"):
            state, mets = step(state, b, one)
            mets = jax.device_get(mets)
        skipped += int(float(mets.get("skipped", 0.0)) > 0)
        i, n = i + 1, n + 1
        if span is not None:
            traced.append(i)
            for k, v in mets.items():
                if np.ndim(v) == 0:
                    step_metrics.setdefault(k, []).append(float(v))
            if len(traced) >= mix["trace_steps"]:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                span = None
        if time.perf_counter() - t0 >= args.seconds and span is None:
            break
    elapsed = time.perf_counter() - t0
    window_compiles = clock.compiles - compiles0
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[train] window steps={n} elapsed_s={elapsed!r} skipped={skipped} "
        f"window_compiles={window_compiles} losses={prog['losses']} "
        f"peak_bytes_in_use={peak}")
    del state, step
    gc.collect()

    t_ref = time.perf_counter()
    want = reference_steps(dims, mix, seed, data)
    checks = compare(prog, want)
    log(f"[check] reference losses={want['losses']} worst leaves "
        f"{checks.pop('_at')} reference {time.perf_counter() - t_ref:.1f}s")
    tokens = mix["batch"] * mix["seq_len"]
    return {
        "setup_s": setup_s, "peak": peak,
        "metrics": {"train_tok_s": n * tokens / elapsed},
        "attempted": n, "failed": skipped, "checks": checks,
        "traced_steps": len(traced), "step_metrics": step_metrics,
        "data": data, "prog": prog,
        "want": want,
    }
