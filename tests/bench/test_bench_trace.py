"""``bench/trace.py`` on small traces recorded on a TPU v5e: two steps
of the test-size fine-tune and two seconds of test-size serving, each
run by the benchmark's own driver with its profiler on.
The reduction finds the traced window, the device's busy time inside
it, the kernels by the name patterns the per-layer readers use, and the
host span each idle gap fell in."""
import pytest

from _bench_path import BENCH, DATA

import run

trace = run.bench_module("trace", BENCH / "trace.py")


def _patterns(name, *attrs):
    mod = run.bench_module(name, BENCH / "metrics" / f"{name}.py")
    return [p for a in attrs for p in getattr(mod, a)]


@pytest.mark.parametrize("kind,readers", [
    ("train", [("grouped_mlp_roofline.train", ("FWD",)),
               ("grouped_mlp_roofline.train", ("BWD",)),
               ("flash_attention_roofline", ("FWD",)),
               ("flash_attention_roofline", ("BWD",))]),
    ("serve", [("grouped_mlp_roofline.serve", ("PATTERNS",)),
               ("decode_attention_roofline", ("PATTERNS",))]),
])
def test_reduction_of_a_chip_trace(kind, readers):
    red = trace.load(DATA / f"tiny_{kind}.xplane.pb")
    assert red.window_s > 0
    assert 0 < red.busy_s <= red.window_s
    ops = red.top_ops()
    assert 0 < len(ops) <= 10
    assert all(s > 0 for _, s in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    # loops count only their own time, so no kind outweighs the busy time
    assert sum(s for _, s in ops) <= red.busy_s * 1.001
    gaps = red.idle_gaps()
    idle = red.window_s - red.busy_s
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6) \
        or len(gaps) == 10
    assert any(name.startswith("bench.") for name, _ in gaps)
    for name, attrs in readers:
        n, secs = red.kernel(_patterns(name, *attrs))
        assert n > 0 and secs > 0, (name, attrs)


# -- self time ----------------------------------------------------------------

def _nested_self_times(ops):
    """Each op less the whole duration of every op that starts inside
    it: the rule before instants were charged to the innermost open op,
    kept here as the reference where ops nest properly."""
    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
        while stack and stack[-1][0].start + stack[-1][0].dur <= o.start:
            stack.pop()
        entry = [o, o.dur]
        if stack:
            stack[-1][1] -= o.dur
        stack.append(entry)
        out.append(entry)
    return [(o, own) for o, own in out]


def _ops(*spans):
    return [trace.Op(name, a, b - a) for name, a, b in spans]


def _own(ops):
    return {o.name: own for o, own in trace.self_times(ops)}


@pytest.mark.parametrize("spans,want", [
    # B starts inside A and outlives it: A loses only the overlap
    ((("A", 0, 10), ("B", 5, 15)), {"A": 5, "B": 10}),
    # C starts inside B, inside A, and outlives both
    ((("A", 0, 10), ("B", 2, 4), ("C", 3, 12)), {"A": 2, "B": 1, "C": 9}),
])
def test_a_partial_overlap_costs_the_earlier_op_only_the_overlap(spans,
                                                                  want):
    assert _own(_ops(*spans)) == want


def test_properly_nested_ops_keep_their_self_times():
    ops = _ops(("loop", 0, 10), ("a", 1, 3), ("b", 3, 6), ("b.in", 4, 5),
               ("c", 7, 7), ("outer", 11, 14), ("inner", 11, 12),
               ("after", 14, 16))
    want = {o.name: own for o, own in _nested_self_times(ops)}
    assert want == {"loop": 5, "a": 2, "b": 2, "b.in": 1, "c": 0,
                    "outer": 2, "inner": 1, "after": 2}
    assert _own(ops) == want


def _clipped_planes(red):
    lo, hi = red.window
    return [(ops, [trace.Op(o.name, max(o.start, lo),
                            min(o.start + o.dur, hi) - max(o.start, lo))
                   for o in ops]) for ops in red.devices]


@pytest.mark.parametrize("recording", ["tiny_train", "tiny_train_scoped"])
def test_no_self_time_is_negative_on_a_recorded_train_trace(recording):
    """Both recordings hold ops that start where the op before them ends,
    which float seconds put inside it: the rule before read those ops
    negative by the later op's whole duration."""
    red = trace.load(DATA / f"{recording}.xplane.pb")
    for _, ops in _clipped_planes(red):
        assert min(own for _, own in _nested_self_times(ops)) < 0
        assert min(own for _, own in trace.self_times(ops)) >= 0


@pytest.mark.parametrize("recording",
                         ["tiny_train", "tiny_train_scoped", "tiny_serve"])
def test_self_times_sum_to_the_busy_time_of_each_plane(recording):
    red = trace.load(DATA / f"{recording}.xplane.pb")
    for raw, ops in _clipped_planes(red):
        busy = sum(b - a for a, b in red.busy_intervals(raw))
        assert sum(own for _, own in trace.self_times(ops)) == \
            pytest.approx(busy, rel=1e-9)
