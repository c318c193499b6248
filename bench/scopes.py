"""The train step's device time by the parts the program names.

The program names its parts with ``jax.named_scope``. A scope changes
only the ``op_name`` metadata of the HLO it encloses, never the compiled
code. The compiled module's text prints that metadata on every
instruction (``%fusion.3 = ... metadata={op_name="jit(train_step)/jvp()/
while/body/closed_call/moe.dispatch/sort"}``), and the device trace names
each op by its instruction, so the text maps a traced op to its path. A
fusion carries the op_name of its root instruction: a fused op belongs
to the scope of its fusion root.

The scopes a split counts are the shared ones, ``SCOPES`` (the program's
parts every family's step has), followed by the family's own: a family
file (``bench/families/<family>.py``) may list in its ``SCOPES`` the
further scope names its program path sets (a latent projection, a
shared expert, a mixer that is not attention). A family scope that
repeats a shared name is refused. Readers take a scope's share from
``of(ctx)`` whichever list it came from.

Each op of the traced window counts its self time under the innermost
listed scope of its path, or ``unscoped``, and in one phase:
``recompute`` where the path passes ``rematted_computation`` (the
forward that ``remat`` runs again inside the backward pass), ``bwd``
where it passes a ``transpose(`` (the backward), ``fwd`` otherwise.
Self time (``trace.self_times``) charges each instant of a device plane
to the innermost op open then, the latest started: a loop's event counts
the time between the events of its body, an op that another outlives
loses only the overlap, no op's self time is negative, and the self
times of a plane sum to its busy time. An op the text does not name is
unresolved; unresolved ops over ``MAX_UNRESOLVED`` of the busy time stop
the run, naming them.

The text comes from compiling the cell's step again after the window,
in traced runs only: the same lowering as the timed step's, so the
persistent compile cache hands back the executable that ran.
"""
from __future__ import annotations

import json
import re
import sys
import time
from dataclasses import dataclass, field

# The shared scopes: the program's parts every family's step has
# (src/repro: models/layers.py, models/attention.py, core/moe.py,
# models/model_zoo.py, training/train_loop.py).
SCOPES = ("embed", "attn", "kv.write", "moe.route", "moe.dispatch",
          "moe.experts", "moe.combine", "lm_head", "loss", "sample",
          "optimizer")
UNSCOPED = "unscoped"
PHASES = ("fwd", "recompute", "bwd")
MAX_UNRESOLVED = 0.01  # of busy time

_INSTRUCTION = re.compile(r"\s*(ROOT )?%?([^\s=]+) = ")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([^\s(]+) .*\{$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_TRANSFORM = re.compile(r"^[\w-]+\((.*)\)$")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its op_name path. An instruction the compiler
    made carries none (the tuple root of a multi-output fusion, a
    rewritten scatter, a copy); it takes the path of its fusion root,
    else of the nearest instruction feeding it that has one, looked for
    breadth first inside its fused computation and then among its
    operands; "" where none is found."""
    comps: dict[str, dict] = {}
    ins = root = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and not line.startswith(" "):
                ins, root = {}, [None]
                comps[c.group(1)] = {"ins": ins, "root": root}
            continue
        if ins is None:
            continue
        name = m.group(2)
        rhs = line[m.end():].split(", metadata=")[0]
        calls = _CALLS.search(rhs)
        p = _OP_NAME.search(line)
        ins[name] = (p.group(1) if p else "", _REF.findall(rhs),
                     calls.group(1) if calls else None)
        if m.group(1):
            root[0] = name
    return {name: _path(comps, comp, name)
            for comp, c in comps.items() for name in c["ins"]}


def _path(comps, comp: str, name: str) -> str:
    ins = comps[comp]["ins"]
    if ins[name][0]:
        return ins[name][0]
    seen, queue = set(), [name]
    while queue:
        n = queue.pop(0)
        if n in seen or n not in ins:
            continue
        seen.add(n)
        op, operands, calls = ins[n]
        if op:
            return op
        if calls in comps and comps[calls]["root"][0]:
            inner = _path(comps, calls, comps[calls]["root"][0])
            if inner:
                return inner
        queue.extend(operands)
    return ""


def scope_of(path: str, scopes: tuple = SCOPES) -> str:
    """The innermost of ``scopes`` on the path. A scope at the top of a
    differentiated function shows wrapped in its transforms
    (``jvp(loss)``, ``transpose(jvp(lm_head))``)."""
    found = UNSCOPED
    for seg in path.split("/"):
        while (m := _TRANSFORM.match(seg)):
            seg = m.group(1)
        if seg in scopes:
            found = seg
    return found


def scopes_of(dims: dict) -> tuple:
    """The shared scopes, then those of the family that ``dims`` names
    (its ``SCOPES``, none where it lists none). A family scope that
    repeats a shared name, ``unscoped`` or one of its own is refused."""
    import model

    family = model.family_of(dims)
    own = tuple(getattr(family, "SCOPES", ()))
    for i, s in enumerate(own):
        clash = ("the shared scope" if s in SCOPES
                 else "the name of unscoped time" if s == UNSCOPED
                 else "its own scope" if s in own[:i] else None)
        if clash:
            raise ValueError(
                f"scopes: family {dims['family']!r} ({family.__file__}) "
                f"lists scope {s!r}, which repeats {clash} {s!r} "
                f"(shared scopes: {SCOPES})")
    return SCOPES + own


def phase_of(path: str) -> str:
    if "rematted_computation" in path:
        return "recompute"
    return "bwd" if "transpose(" in path else "fwd"


@dataclass
class Split:
    """Device seconds of the traced window by (scope, phase), averaged
    over the device planes, and of the ops the text does not name."""
    busy_s: float
    scopes: tuple = SCOPES  # in the order the table lists them
    seconds: dict[tuple[str, str], float] = field(default_factory=dict)
    unresolved: dict[str, float] = field(default_factory=dict)

    @property
    def unresolved_s(self) -> float:
        return sum(self.unresolved.values())

    def share(self, scopes=None, phases=PHASES) -> float:
        """Percent of busy time under ``scopes`` (all, with None) in
        ``phases``."""
        s = sum(v for (sc, ph), v in self.seconds.items()
                if (scopes is None or sc in scopes) and ph in phases)
        return 100.0 * s / self.busy_s

    def table(self) -> dict:
        rows = {}
        for (sc, ph), v in self.seconds.items():
            rows.setdefault(sc, dict.fromkeys(PHASES, 0.0))[ph] += v
        order = [s for s in self.scopes + (UNSCOPED,) if s in rows]
        return {s: rows[s] for s in order}

    def line(self, **extra) -> str:
        return "[trace] by scope " + json.dumps(
            {"busy_s": self.busy_s, "unresolved_s": self.unresolved_s,
             **extra, "seconds": self.table()})


def split(red, names: dict[str, str], scopes: tuple = SCOPES) -> Split:
    """``red``: a ``trace.Reduced``; ``names``: ``op_names`` of the
    module that ran in its window; ``scopes``: the scopes to count, as
    ``scopes_of`` gives them."""
    # the module that reduced the trace (bench/trace.py)
    trace = sys.modules[type(red).__module__]
    lo, hi = red.window
    out = Split(red.busy_s, scopes)
    k = len(red.devices)
    for ops in red.devices:
        clipped = [trace.Op(o.name, max(o.start, lo),
                            min(o.start + o.dur, hi) - max(o.start, lo))
                   for o in ops]
        for o, own in trace.self_times(clipped):
            if o.name not in names:
                out.unresolved[o.kind] = out.unresolved.get(o.kind, 0.0) \
                    + own / k
                continue
            path = names[o.name]
            key = (scope_of(path, scopes), phase_of(path))
            out.seconds[key] = out.seconds.get(key, 0.0) + own / k
    return out


def train_step_hlo(conf: dict, mix: dict) -> str:
    """The compiled text of the training cell's step: ``train.build_step``
    lowered at the abstract shapes of the state and batch the cell runs
    (the timed step's own lowering, so a compile-cache hit)."""
    import jax
    import jax.numpy as jnp

    import model
    import train
    from repro.training.train_loop import init_train_state

    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    opt, step = train.build_step(cfg, mix)
    key = model.key_of(0)
    make_w = model.program_weights(cfg, dims)
    state = jax.eval_shape(
        lambda: init_train_state(key, cfg, opt, params=make_w(key)))
    batch = train.Data(mix, 0, dims["V"]).batch(0, device=False)
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         batch)
    one = jax.ShapeDtypeStruct((), jnp.float32)
    return step.lower(state, batch, one).compile().as_text()


def of(ctx):
    """The traced training window's split, made once per run and kept on
    ``ctx``; None outside a traced training run. ``ctx.hlo``, where
    present, is the compiled text (recorded traces); otherwise the step
    is compiled again here. Prints the ``[trace] by scope`` line."""
    if not ctx.res.get("traced_steps") or ctx.trace.busy_s <= 0:
        return None
    got = getattr(ctx, "scopes", None)
    if got is None:
        t0 = time.perf_counter()
        text = getattr(ctx, "hlo", None) or train_step_hlo(ctx.conf,
                                                             ctx.mix)
        got = ctx.scopes = split(ctx.trace, op_names(text),
                                 scopes_of(ctx.dims))
        print(got.line(hlo_s=time.perf_counter() - t0), file=sys.stderr,
              flush=True)
        if got.unresolved_s > MAX_UNRESOLVED * got.busy_s:
            worst = sorted(got.unresolved.items(), key=lambda kv: -kv[1])
            raise SystemExit(
                f"scopes: ops the compiled step does not name take "
                f"{got.unresolved_s!r} s of {got.busy_s!r} s busy, over "
                f"{MAX_UNRESOLVED:.0%}: {worst[:10]}")
    return got
