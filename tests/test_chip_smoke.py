"""chip_smoke.py's phases end to end on the CPU, at the reduced granite
config with the Pallas kernels in interpret mode — the same functions
the script drives on the chip, where only ``main()`` demands a TPU."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_one_chip_phases_reduced_granite_interpret():
    lines = []
    rep = chip_smoke.one_chip(
        chip_smoke.granite(reduced=True), seed=0, impl="pallas", steps=3,
        batch=2, seq=16, remat="full", prompt_lens=(7, 13, 5, 9),
        max_new=4, block_size=8, n_decode_check=2, log=lines.append,
    )
    tr, sv = rep["train"], rep["serve"]
    assert len(tr["losses"]) == 3
    assert tr["loss_diff"] <= chip_smoke.LOSS_ATOL
    assert sv["completed"] == 4
    # f32 interpret mode on the CPU: the kernels match the reference to
    # float32 roundoff, far inside the chip's bf16-precision tolerance.
    assert sv["rel_diff"] < 1e-4
    assert any(line.startswith("[serve] 4/4 requests completed")
               for line in lines)


def test_main_refuses_without_tpu(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "needs a TPU" in out.err


EP_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    from repro.launch.mesh import make_debug_mesh

    cfg = chip_smoke.granite(reduced=True)
    rep = chip_smoke.ep_step_vs_one_device(
        cfg, chip_smoke.upcycle(cfg, 0), mesh=make_debug_mesh((2, 2)),
        impl="pallas", batch=4, seq=32, remat="full",
    )
    assert rep["rel_diff"] <= chip_smoke.EP_LOSS_RTOL, rep
    print("EP_OK")
    """
)


def test_ep_phase_on_four_cpu_devices():
    """The --chips 4 phase on forced CPU devices (the count must be set
    before JAX starts, hence the child; it runs on the CPU backend)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", EP_SCRIPT, str(ROOT)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EP_OK" in r.stdout


def test_compile_cache_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = compile_cache.enable_compile_cache()
        assert placed == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
