"""Operation and byte counts against hand counts at granite widths, and
the peak table."""
import pytest

from _bench_path import BENCH, load

import flops
import model

G1 = model.dims_of(load(BENCH / "configs/granite-moe-1b-a400m.json"))
G3 = model.dims_of(load(BENCH / "configs/granite-moe-3b-a800m.json"))
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_params_per_token():
    # attention q,k,v,o + 8 experts x 3 matrices + router, per layer
    attn1 = 1024 * (16 + 8 + 8) * 64 + 16 * 64 * 1024
    assert flops.matmul_params_per_token(G1) == 24 * (
        attn1 + 8 * 3 * 1024 * 512 + 1024 * 32)
    attn3 = 1536 * (24 + 8 + 8) * 64 + 24 * 64 * 1536
    assert flops.matmul_params_per_token(G3) == 8 * (
        attn3 + 8 * 3 * 1536 * 512 + 1536 * 40)


def test_train_step_flops_3b():
    tokens = 2 * 4096
    per_tok = 2 * flops.matmul_params_per_token(G3) + 2 * 1536 * 49155
    attn = 8 * 2 * 24 * 64 * 4096 * 4096 * 2  # L*2*H*dh*S^2 per sequence
    assert flops.train_step_flops(G3, 2, 4096) == pytest.approx(
        3 * (tokens * per_tok + attn))


def test_grouped_mlp_counts():
    rows = 64 * 8  # a full decode batch's assignments
    f, b = flops.grouped_mlp_fwd(G1, rows, 4)
    assert f == 6 * rows * 1024 * 512
    touched = 32 * (1 - (31 / 32) ** rows)
    assert b == pytest.approx(4 * (touched * 3 * 1024 * 512
                                   + 2 * rows * 1024))
    assert touched == pytest.approx(32, abs=1e-5)
    fb, bb = flops.grouped_mlp_bwd(G3, 65536, 4)
    assert fb == 12 * 65536 * 1536 * 512
    assert bb == pytest.approx(4 * (40 * 6 * 1536 * 512 + 3 * 65536 * 1536))
    # one row touches one expert
    assert flops.touched_experts(32, 1) == pytest.approx(1.0)


def test_decode_attention_counts():
    f, b = flops.decode_attention(G1, [100, 300], 4)
    assert f == 4 * 16 * 64 * 400
    assert b == 4 * (2 * 8 * 64 * 400 + 2 * 2 * 16 * 64)
    # 400 keys of K and V in float32 at 819 GB/s: memory bound
    assert flops.least_time(f, b, PEAK) == pytest.approx(b / 819e9)


def test_flash_counts():
    f, b = flops.flash_fwd(G3, 2, 4096, 4)
    assert f == 2 * 2 * 24 * 4096 * 4096 * 64
    assert b == 4 * 2 * 4096 * (48 + 16) * 64
    fb, _ = flops.flash_bwd(G3, 2, 4096, 4)
    assert fb == 2.5 * f
    assert flops.least_time(f, b, PEAK) == pytest.approx(f / 197e12)


def test_peak_table_keys_and_unknown_kind():
    import run

    peaks = load(BENCH / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = run.peak_of("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.peak_of("TPU v9 imaginary")
