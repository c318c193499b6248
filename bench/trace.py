"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-kernel time by name pattern, and the ``breakdown`` of a result line.

Device planes are ``/device:TPU:<n>``; their op events sit on the
``XLA Ops`` line, each named by its HLO instruction
(``%_grouped_mlp_pallas_tables.24 = f32[...] custom-call(...)``; a
Pallas kernel's instruction takes the name of the jitted function that
calls it), and a loop's event spans the events of its body. Host
spans come from the ``/host:CPU`` plane: the benchmark's own
``TraceAnnotation``s (``bench.*``) and whatever JAX records beside them.
The traced window is the ``bench.traced`` host span: device time is
clipped to it.
"""
from __future__ import annotations

import bisect
import heapq
import math
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. "fusion.3"
    start: float  # seconds, on the trace's clock
    dur: float

    @property
    def kind(self) -> str:
        """The name without its instance number: ``fusion``,
        ``_grouped_mlp_pallas_tables``."""
        return _INSTANCE.sub("", self.name)


@dataclass
class Reduced:
    window: tuple[float, float]
    devices: list[list[Op]]  # per device plane, ops inside the window
    host: list[tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def __post_init__(self):
        self.host.sort()
        self._starts = [a for a, _, _ in self.host]

    def busy_intervals(self, ops: list[Op]) -> list[tuple[float, float]]:
        lo, hi = self.window
        iv = sorted((max(o.start, lo), min(o.start + o.dur, hi))
                    for o in ops)
        merged: list[list[float]] = []
        for a, b in iv:
            if b <= a:
                continue
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the device planes."""
        tot = [sum(b - a for a, b in self.busy_intervals(ops))
               for ops in self.devices]
        return sum(tot) / len(tot)

    def kernel(self, patterns: list[str]) -> tuple[int, float]:
        """(events, device seconds) of ops whose name matches any of
        ``patterns`` (regular expressions), averaged over devices."""
        rx = re.compile("|".join(f"(?:{p})" for p in patterns))
        n, s = 0, 0.0
        for ops in self.devices:
            for o in ops:
                if rx.search(o.name):
                    n += 1
                    s += o.dur
        k = len(self.devices)
        return n // k, s / k

    def top_ops(self, n: int = 10) -> list[list]:
        """Device seconds by kind of op on device 0, largest first; an op
        that encloses others (a loop) counts only its own time."""
        by: dict[str, float] = {}
        for o, own in self_times(self.devices[0]):
            by[o.kind] = by.get(o.kind, 0.0) + own
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def host_at(self, t: float) -> str:
        """The innermost host span covering ``t``: the one that started
        last among those still open (``host`` is sorted by start)."""
        i = bisect.bisect_right(self._starts, t)
        while i > 0:
            i -= 1
            a, b, name = self.host[i]
            if b > t:
                return name
        return "no host span"

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds of device 0 grouped by the host span each gap's
        midpoint fell in, largest first."""
        lo, hi = self.window
        iv = self.busy_intervals(self.devices[0])
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        by: dict[str, float] = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                lab = self.host_at((a + b) / 2)
                by[lab] = by.get(lab, 0.0) + (b - a)
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]


_INSTANCE = re.compile(r"\.\d+$")
_INSTRUCTION = re.compile(r"%?([^\s=]+) = ")


def self_times(ops: list[Op]) -> list[tuple[Op, float]]:
    """Each op of one plane with its self time: every instant is charged
    to the innermost op open then, the latest started (of two starting
    together, the shorter) among those still running. An op's self time
    is so its duration less the parts of it that ops starting inside it
    cover, each part clipped to the op's own end: never negative, and
    over the plane the self times sum to the union of the op intervals.
    Where ops nest properly, that is each op's duration less those of
    the ops it encloses. Where an op outlives the op it started in (one
    that starts where the op before it ends, which rounding the trace's
    nanoseconds to float seconds can put inside that op, or one that
    truly overlaps it), the earlier op loses only the overlap, not the
    later op's whole duration."""
    order = sorted(ops, key=lambda o: (o.start, -o.dur))
    own = [0.0] * len(order)
    open_: list[tuple[int, float]] = []  # (-rank, end): innermost first
    t = -math.inf

    def charge(until):
        nonlocal t
        while open_ and t < until:
            rank, end = open_[0]
            if end <= t:
                heapq.heappop(open_)
                continue
            nxt = min(end, until)
            own[-rank] += nxt - t
            t = nxt
        t = max(t, until)

    for rank, o in enumerate(order):
        charge(o.start)
        heapq.heappush(open_, (-rank, o.start + o.dur))
    charge(math.inf)
    return list(zip(order, own))


def _op_name(ev) -> str:
    m = _INSTRUCTION.match(ev.name)
    return m.group(1) if m else ev.name


def load(path: Path) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    host: list[tuple[float, float, str]] = []
    devices: list[list[Op]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            # only the thread that drives the benchmark: its line holds
            # the bench.* spans
            for line in plane.lines:
                evs = [(ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                       for ev in line.events]
                if not any(n.startswith("bench.") for _, _, n in evs):
                    continue
                for a, b, name in evs:
                    if name == WINDOW_SPAN:
                        window = (a, b)
                    elif b > a:
                        host.append((a, b, name))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = [Op(_op_name(ev), ev.start_ns * 1e-9,
                      ev.duration_ns * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices.append(ops)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    lo, hi = window
    devices = [[o for o in ops if o.start < hi and o.start + o.dur > lo]
               for ops in devices]
    return Reduced(window, devices, host)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
