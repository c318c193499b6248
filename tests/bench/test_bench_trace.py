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
