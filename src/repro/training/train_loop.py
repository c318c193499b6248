"""Self-healing training loop: jitted train_step (grad accumulation,
compression, remat), divergence rollback, bit-exact crash-resume,
preemption handling.

``make_train_step`` builds a pure (state, batch[, lr_scale]) ->
(state, metrics) function; distribution comes entirely from in/out
shardings + the logical constraints inside the model (GSPMD) — the same
function serves 1 CPU device and a 512-chip mesh.

``Trainer`` is the fault-tolerant driver. Failure modes it survives
(the train-side mirror of the serve stack's table in
``repro/serve/__init__.py``; overview in ``repro/training/__init__``):

* **finite loss spike** (divergence) — the :class:`SpikeDetector`
  flags ``loss > spike_threshold × trailing median``; the Trainer
  restores the last known-good checkpoint, fast-forwards the data
  iterator past the offending batch window (PaLM-style batch skip),
  optionally decays the LR for a cooldown, and aborts with the full
  rollback history after ``max_rollbacks``;
* **NaN/inf loss** — the in-step non-finite guard drops the update
  (params/opt state/residual keep their old values) at zero extra host
  syncs; abort after ``max_consecutive_skips`` consecutive skips;
* **crash / kill** — every checkpoint carries ALL resume-relevant
  state (data-iterator position, skip counters, rollback history, LR
  cooldown, detector window) so kill-at-step-k + auto-resume is
  bit-identical to an uninterrupted run (tests/test_train_chaos.py);
* **preemption** — cooperative SIGTERM: final blocking save + clean
  exit; the restarted job resumes;
* **flaky / corrupt checkpoint store** — the CheckpointManager retries
  transient IO with capped backoff and ``restore_latest`` falls back
  past torn payloads to the last known-good step.

Fault injection for all of the above lives in
``repro.training.chaos`` (:class:`TrainChaosConfig` + ``run_chaotic``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import DataIterator
from repro.obs.tracker import NULL, Tracker
from repro.models import model_zoo as zoo
from repro.models import param as pm
from repro.optim.base import Optimizer, apply_updates, global_norm
from repro.sharding import ShardCtx, act
from repro.training import compression
from repro.training.chaos import ChaosState, SimulatedCrash, TrainChaosConfig
from repro.training.health import SpikeDetector


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    grad_accum: int = 1
    compression: str = "none"  # none | bf16 | int8
    checkpoint_every: int = 100
    log_every: int = 10
    max_to_keep: int = 3
    # straggler watchdog: warn when a step takes > factor * median
    straggler_factor: float = 3.0
    # Non-finite loss guard: a NaN/inf loss or grad norm skips the
    # optimizer update (params/opt state/residual keep their old
    # values, the step counter still advances — MoE router blowups are
    # the classic upcycling fine-tune failure); the Trainer aborts with
    # a clear error after this many CONSECUTIVE skips. 0 disables the
    # guard entirely (step applies whatever it computed).
    max_consecutive_skips: int = 10
    # Divergence (FINITE loss spike) detection + rollback. A loss >
    # spike_threshold × trailing baseline (median of the last
    # spike_window finite losses, armed after spike_min_history steps)
    # triggers restore-from-last-known-good + a batch-window skip.
    # 0.0 disables detection (default — short smoke runs with jumpy
    # early losses opt in explicitly).
    spike_threshold: float = 0.0
    spike_window: int = 32
    spike_min_history: int = 5
    spike_mode: str = "median"  # median | ewma
    # Rollback policy: skip the data stream to offending_batch +
    # rollback_skip (the PaLM-style window skip — the bad batch never
    # recurs), decay LR by rollback_lr_decay for rollback_cooldown
    # steps after the restore, and abort with the full rollback
    # history after max_rollbacks rollbacks.
    max_rollbacks: int = 3
    rollback_skip: int = 8
    rollback_lr_decay: float = 1.0
    rollback_cooldown: int = 0


def make_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    *,
    ac: zoo.ApplyCfg = zoo.ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
    tc: TrainConfig = TrainConfig(),
):
    """Returns train_step(state, batch[, lr_scale]) -> (state, metrics).

    Kernel implementations come from ``ac`` (ApplyCfg): the default
    "auto" resolves here — at step-build time, so the jitted step traces
    with a concrete choice — to the fused Pallas forward+backward kernels
    on TPU and the XLA einsum path on CPU.

    ``lr_scale`` (optional traced scalar) multiplies the optimizer
    updates — the post-rollback LR-cooldown knob. The Trainer always
    passes it as a jnp scalar so the jitted step keeps ONE signature
    (no retrace when the scale changes); omitting it traces without the
    multiply, preserving the original two-arg call.
    """
    ac = ac.resolve()

    def grads_of(params, batch):
        (loss, mets), grads = jax.value_and_grad(
            zoo.loss_fn, has_aux=True
        )(params, batch, cfg, ac=ac, ctx=ctx)
        return grads, mets

    def train_step(state, batch, lr_scale=None):
        params = state["params"]
        if tc.grad_accum > 1:
            def micro(carry, mb):
                g_acc, m_acc = carry
                g, m = grads_of(params, mb)
                return (
                    jax.tree.map(jnp.add, g_acc, g),
                    jax.tree.map(jnp.add, m_acc, m),
                ), None

            def reshape(x):
                b = x.shape[0]
                return x.reshape(
                    (tc.grad_accum, b // tc.grad_accum) + x.shape[1:]
                )

            micro_batches = jax.tree.map(reshape, batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            from repro.models.stack import zero_metrics

            m0 = dict(zero_metrics())
            m0.update(loss=jnp.zeros(()), ce=jnp.zeros(()))
            (grads, mets), _ = jax.lax.scan(
                micro, (g0, m0), micro_batches
            )
            grads = jax.tree.map(lambda g: g / tc.grad_accum, grads)
            mets = jax.tree.map(lambda m: m / tc.grad_accum, mets)
        else:
            grads, mets = grads_of(params, batch)

        if tc.compression != "none":
            grads, residual = compression.compress(
                grads, state["residual"], tc.compression
            )
        else:
            residual = state.get("residual")

        # Update, norm and skip guard: the "optimizer" part of a step's
        # device profile.
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state["opt_state"], params
            )
            if lr_scale is not None:
                updates = jax.tree.map(lambda u: u * lr_scale, updates)
            new_params = apply_updates(params, updates)
            mets = dict(mets)
            grad_norm = global_norm(grads)
            mets["grad_norm"] = grad_norm
            if tc.max_consecutive_skips > 0:
                # Non-finite guard: keep the OLD params/opt state/
                # residual when the loss or grad norm blew up — all
                # inside the jitted step (jnp.where), zero extra host
                # syncs; the Trainer reads mets["skipped"] off the
                # metrics it already pulls.
                ok = jnp.isfinite(mets["loss"]) & jnp.isfinite(grad_norm)

                def pick(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )

                new_params = pick(new_params, params)
                opt_state = pick(opt_state, state["opt_state"])
                if residual is not None and "residual" in state:
                    residual = pick(residual, state["residual"])
                mets["skipped"] = (~ok).astype(jnp.float32)
            else:
                mets["skipped"] = jnp.zeros((), jnp.float32)
        new_state = dict(state)
        new_state.update(
            params=new_params,
            opt_state=opt_state,
            # The step counter tracks consumed batches, so checkpoint /
            # resume bookkeeping is oblivious to skipped updates.
            step=state["step"] + 1,
        )
        if residual is not None:
            new_state["residual"] = residual
        return new_state, mets

    return train_step


def init_train_state(
    rng,
    cfg: ArchConfig,
    optimizer: Optimizer,
    *,
    dtype=jnp.float32,
    tc: TrainConfig = TrainConfig(),
    params: Any = None,
):
    """params: optional pre-built plain-array tree (e.g. upcycled)."""
    if params is None:
        wrapped = zoo.init_params(rng, cfg, dtype=dtype)
        params, _ = pm.split(wrapped)
    state = {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if tc.compression != "none":
        state["residual"] = compression.init_residual(params)
    return state


def state_axes(cfg: ArchConfig, *, dtype=jnp.float32,
               tc: TrainConfig = TrainConfig()):
    """Logical-axes tree matching init_train_state's structure."""
    wrapped = jax.eval_shape(
        lambda: zoo.init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    )
    vals, axes = pm.split(wrapped)
    opt_axes = {
        "step": "",
        "slots": _adafactor_slot_axes(axes, vals),
    }
    out = {"params": axes, "opt_state": opt_axes, "step": ""}
    if tc.compression != "none":
        out["residual"] = axes
    return out


def _adafactor_slot_axes(axes_tree, shapes_tree):
    """Map param logical axes -> adafactor slot axes ({v_row, v_col} or
    {v}); mirrors optim/adafactor._factored exactly."""
    from repro.optim.adafactor import _factored

    def one(a: str, shaped):
        names = a.split() if a else []
        if _factored(tuple(shaped.shape)):
            return {
                "v_row": " ".join(names[:-1]),
                "v_col": " ".join(names[:-2] + names[-1:]),
            }
        return {"v": a}

    return jax.tree.map(one, axes_tree, shapes_tree)


class PreemptionSignal:
    """Cooperative preemption flag (SIGTERM handler or test hook)."""

    def __init__(self):
        self._flag = False

    def install(self):
        import signal

        def handler(signum, frame):
            self._flag = True

        signal.signal(signal.SIGTERM, handler)
        return self

    def trigger(self):
        self._flag = True

    def __bool__(self):
        return self._flag


@dataclasses.dataclass
class Trainer:
    cfg: ArchConfig
    optimizer: Optimizer
    data: DataIterator
    ckpt_dir: str
    ac: zoo.ApplyCfg = zoo.ApplyCfg()
    ctx: Optional[ShardCtx] = None
    tc: TrainConfig = TrainConfig()
    preemption: Optional[PreemptionSignal] = None
    log_fn: Callable[[str], None] = print
    # Observability: one "train" row per step (loss / ce / grad_norm /
    # skipped_steps / spike / rollbacks / lr_scale / step_ms) plus
    # checkpoint retry/fallback counters — log_fn keeps the old
    # print-style behaviour alongside.
    tracker: Optional[Tracker] = None
    # Seeded fault injection (repro/training/chaos.py). chaos_state is
    # harness-owned so its ledger survives simulated process crashes;
    # a bare chaos config gets a private state.
    chaos: Optional[TrainChaosConfig] = None
    chaos_state: Optional[ChaosState] = None

    def __post_init__(self):
        self.trk = self.tracker if self.tracker is not None else NULL
        if self.chaos is not None and self.chaos_state is None:
            self.chaos_state = ChaosState(self.chaos)
        self.manager = CheckpointManager(
            self.ckpt_dir, max_to_keep=self.tc.max_to_keep,
            tracker=self.trk,
            fault_hook=(self.chaos_state.fault_hook
                        if self.chaos_state is not None else None),
        )
        self._step_times: list[float] = []
        self.detector = SpikeDetector(
            self.tc.spike_threshold, window=self.tc.spike_window,
            min_history=self.tc.spike_min_history,
            mode=self.tc.spike_mode,
        )
        self._skipped_steps = 0
        self._consecutive_skips = 0
        self._rollbacks: list[dict] = []
        self._cooldown_left = 0
        self.stats: dict = {}

    # -- resume-relevant trainer state ----------------------------------
    # Everything the loop needs beyond the param/opt tree rides in
    # checkpoint metadata, so kill-at-step-k + resume replays
    # bit-identically: data-iterator position (+ skip history), skip
    # counters, rollback history, LR cooldown, detector window.
    def _trainer_meta(self) -> dict:
        return {
            "skipped_steps": self._skipped_steps,
            "consecutive_skips": self._consecutive_skips,
            "rollbacks": list(self._rollbacks),
            "cooldown_left": self._cooldown_left,
            "detector": self.detector.state(),
        }

    def _restore_trainer_meta(self, meta: dict, *,
                              keep_rollbacks: bool = False) -> None:
        tm = meta.get("trainer", {})
        self._skipped_steps = int(tm.get("skipped_steps", 0))
        self._consecutive_skips = int(tm.get("consecutive_skips", 0))
        if not keep_rollbacks:
            self._rollbacks = list(tm.get("rollbacks", []))
        self._cooldown_left = int(tm.get("cooldown_left", 0))
        self.detector.restore(tm.get("detector", {}))

    def _save(self, step: int, state, *, blocking: bool) -> None:
        self.manager.save(
            step, state,
            metadata={"data": self.data.state(),
                      "arch": self.cfg.name,
                      "trainer": self._trainer_meta()},
            blocking=blocking,
        )

    # -- divergence rollback --------------------------------------------
    def _rollback(self, like, bad_step: int, bad_batch: int,
                  obs_loss: float):
        """Restore the last known-good checkpoint, rewind the trainer
        bookkeeping to that checkpoint's view, and fast-forward the
        data iterator past the offending batch window. Returns the
        restored state tree."""
        base = self.detector.baseline()
        self.manager.wait()  # an async save may still be writing
        restored, gstep, meta = self.manager.restore_latest(like)
        if restored is None:
            raise RuntimeError(
                f"training diverged at step {bad_step} "
                f"(loss={obs_loss:.6g}, baseline={base}) and no valid "
                "checkpoint exists to roll back to — every candidate "
                "was corrupt or missing"
            )
        # Rewind bookkeeping to the checkpoint's view — but the
        # rollback HISTORY is cumulative across the run (the
        # max_rollbacks bound must see every rollback, including ones
        # newer than the restored step).
        self.data.restore(meta.get("data", {"step": gstep}))
        self._restore_trainer_meta(meta, keep_rollbacks=True)
        # PaLM-style batch-window skip: the stream resumes PAST the
        # offending batch, so a deterministic bad batch cannot re-fire.
        skip_to = bad_batch + max(1, self.tc.rollback_skip)
        if skip_to > self.data.step:
            self.data.skip(skip_to - self.data.step)
        self._cooldown_left = max(0, self.tc.rollback_cooldown)
        rec = {
            "step": int(bad_step),
            "loss": float(obs_loss),
            "baseline": None if base is None else float(base),
            "restored_to": int(gstep),
            "batch": int(bad_batch),
            "data_skipped_to": int(self.data.step),
        }
        self._rollbacks.append(rec)
        self.trk.count("train.rollbacks", t=bad_step)
        self.trk.event("rollback", t=bad_step, **rec)
        self.log_fn(
            f"[trainer] step {bad_step} DIVERGED "
            f"(loss={obs_loss:.4g} > {self.tc.spike_threshold:g}× "
            f"baseline {0.0 if base is None else base:.4g}); rolled "
            f"back to step {gstep}, data skipped to batch "
            f"{self.data.step} ({len(self._rollbacks)}/"
            f"{self.tc.max_rollbacks} rollbacks)"
        )
        return restored, gstep

    def _abort_diverged(self, bad_step: int, obs_loss: float) -> None:
        base = self.detector.baseline()
        hist = "; ".join(
            f"step {r['step']}: loss {r['loss']:.4g} -> restored to "
            f"{r['restored_to']}, skipped to batch "
            f"{r['data_skipped_to']}" for r in self._rollbacks
        )
        raise RuntimeError(
            f"training diverged: loss spike at step {bad_step} "
            f"(loss={obs_loss:.6g} > {self.tc.spike_threshold:g}× "
            f"baseline {0.0 if base is None else base:.6g}) after "
            f"{len(self._rollbacks)} rollbacks "
            f"[{hist}] — lower the learning rate, widen "
            "rollback_skip past the bad data window, or raise router "
            "z-loss before resuming"
        )

    # -- chaos audit -----------------------------------------------------
    def audit(self, step: int) -> None:
        """Per-step invariant audit (chaos harness): bookkeeping the
        self-healing machinery relies on must hold after every step,
        rollback, resume, and fault."""
        assert len(self.detector.history) <= self.detector.window
        assert len(self._rollbacks) <= self.tc.max_rollbacks
        assert 0 <= self._cooldown_left <= max(
            0, self.tc.rollback_cooldown)
        assert self.data.step >= step, (
            f"data iterator at batch {self.data.step} is behind "
            f"optimizer step {step}"
        )
        steps = self.manager.all_steps()
        assert steps == sorted(set(steps))
        assert self._consecutive_skips <= self._skipped_steps \
            or self._skipped_steps == 0
        if self.chaos_state is not None:
            self.chaos_state.audits += 1

    def run(self, num_steps: int, *, rng=None, init_params=None) -> dict:
        rng = jax.random.PRNGKey(0) if rng is None else rng
        state = init_train_state(
            rng, self.cfg, self.optimizer, tc=self.tc, params=init_params
        )
        # ---- auto-resume -------------------------------------------------
        restored, step0, meta = self.manager.restore_latest(state)
        if restored is not None:
            state = restored
            self.data.restore(meta.get("data", {"step": step0}))
            self._restore_trainer_meta(meta)
            self.log_fn(f"[trainer] resumed from step {step0}")
        train_step = jax.jit(
            make_train_step(
                self.cfg, self.optimizer, ac=self.ac, ctx=self.ctx,
                tc=self.tc,
            ),
            donate_argnums=(0,),
        )
        self._train_step = train_step
        # Rollback anchor: divergence before the first periodic save
        # still needs a known-good restore target.
        if self.detector.enabled and self.manager.latest_step() is None:
            self._save(0, state, blocking=True)
        mets = {}
        step = int(state["step"])
        while step < num_steps:
            i = step
            batch = next(self.data)
            bidx = self.data.step - 1  # index of the batch just consumed
            lr_scale = (self.tc.rollback_lr_decay
                        if self._cooldown_left > 0 else 1.0)
            t0 = time.perf_counter()
            # lr_scale rides as a TRACED jnp scalar: one jit signature
            # for the whole run — cooldown decay never retraces.
            state, mets = train_step(state, batch,
                                     jnp.float32(lr_scale))
            # ONE host pull per step: device_get materialises every
            # metric at once (blocking until the step finishes), so the
            # guard, the tracker, and the log_every print below all
            # read host floats — the old block_until_ready + repeated
            # float(...) shape synced the device once per metric read.
            mets = jax.device_get(mets)
            dt = time.perf_counter() - t0
            self._watchdog(i, dt)
            obs_loss = float(mets["loss"])
            if self.chaos_state is not None \
                    and self.chaos_state.spike_at(bidx):
                obs_loss = obs_loss * self.chaos.spike_scale
            skipped = float(mets.get("skipped", 0.0)) > 0
            # Non-finite guard bookkeeping: "skipped" rides the metrics
            # pull the loop already blocks on — no extra syncs.
            if skipped:
                self._skipped_steps += 1
                self._consecutive_skips += 1
                self.log_fn(
                    f"[trainer] step {i + 1} SKIPPED non-finite update "
                    f"(loss={float(mets['loss'])}, "
                    f"grad_norm={float(mets['grad_norm'])}; "
                    f"{self._consecutive_skips} consecutive)"
                )
                if (self.tc.max_consecutive_skips > 0
                        and self._consecutive_skips
                        >= self.tc.max_consecutive_skips):
                    raise RuntimeError(
                        f"training diverged: {self._consecutive_skips} "
                        "consecutive non-finite losses (last loss="
                        f"{float(mets['loss'])}, grad_norm="
                        f"{float(mets['grad_norm'])}) — lower the "
                        "learning rate, raise router z-loss, or resume "
                        "from the last checkpoint with a different "
                        "data seed"
                    )
            else:
                self._consecutive_skips = 0
            mets["skipped_steps"] = self._skipped_steps
            spike = (not skipped) and self.detector.is_spike(obs_loss)
            # Tracker: every step, not just every log_every — spike
            # steps included (their row precedes the rollback).
            self.trk.row(
                "train", t=i + 1,
                loss=obs_loss, ce=float(mets["ce"]),
                grad_norm=float(mets["grad_norm"]),
                skipped=float(mets.get("skipped", 0.0)),
                skipped_steps=self._skipped_steps,
                spike=float(spike),
                rollbacks=len(self._rollbacks),
                lr_scale=lr_scale,
                step_ms=dt * 1e3,
            )
            if skipped:
                self.trk.count("train.skipped_steps", t=i + 1)
            if spike:
                # Divergence: restore last-known-good + batch-window
                # skip, or abort with the full history once the
                # rollback budget is spent.
                if len(self._rollbacks) >= self.tc.max_rollbacks:
                    self._abort_diverged(i + 1, obs_loss)
                state, step = self._rollback(state, i + 1, bidx,
                                             obs_loss)
                if self.chaos is not None and self.chaos.audit:
                    self.audit(step)
                continue
            self.detector.update(obs_loss)
            if self._cooldown_left > 0:
                self._cooldown_left -= 1
            step = i + 1
            if step % self.tc.log_every == 0:
                self.log_fn(
                    f"[trainer] step {step} loss={float(mets['loss']):.4f} "
                    f"ce={float(mets['ce']):.4f} {dt * 1e3:.0f}ms"
                )
            if self.chaos_state is not None and self.preemption is not None \
                    and self.chaos_state.preempt_at(step):
                self.preemption.trigger()
            # A chaos crash fires BEFORE this step's checkpoint — the
            # worst case: everything since the last save is lost and
            # must replay bit-identically on resume.
            if self.chaos_state is not None \
                    and self.chaos_state.crash_at(step):
                raise SimulatedCrash(f"chaos: crash after step {step}")
            want_ckpt = step % self.tc.checkpoint_every == 0
            if want_ckpt or self.preemption:
                self._save(step, state, blocking=bool(self.preemption))
                if self.chaos_state is not None:
                    self.chaos_state.maybe_corrupt(self.manager, step)
            if self.chaos is not None and self.chaos.audit:
                self.audit(step)
            if self.preemption:
                self.log_fn(
                    f"[trainer] preempted at step {step}; "
                    "checkpoint saved, exiting cleanly"
                )
                break
        self.manager.wait()
        self.stats = {
            "skipped_steps": self._skipped_steps,
            "rollbacks": list(self._rollbacks),
            "cooldown_left": self._cooldown_left,
            "resumed_from": step0,
            # Rollback restores state without retracing: ONE signature
            # for the whole run, rollbacks and LR cooldowns included.
            "compile_count": train_step._cache_size(),
            "store": self.manager.health(),
        }
        return {"state": state, "metrics": mets, "stats": self.stats}

    def _watchdog(self, step: int, dt: float) -> None:
        self._step_times.append(dt)
        if len(self._step_times) < 8:
            return
        med = float(np.median(self._step_times[-64:]))
        if dt > self.tc.straggler_factor * med:
            self.log_fn(
                f"[trainer][straggler] step {step} took {dt * 1e3:.0f}ms "
                f"(median {med * 1e3:.0f}ms) — on a pod this triggers the "
                "slow-host report"
            )
