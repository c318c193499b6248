"""Sharding rules engine tests (AbstractMesh — no devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.sharding import make_rules, spec_for


def mesh2():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh3():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def pr(mesh, **kw):
    return make_rules(mesh, params=True, **kw)


def ar(mesh, **kw):
    return make_rules(mesh, params=False, **kw)


def test_expert_weights_ep_plus_fsdp():
    m = mesh2()
    # granite: E=32 divides model=16 -> EP over model, FSDP over data
    assert spec_for("expert embed mlp", (32, 1024, 512), m, pr(m)) == \
        P("model", "data")


def test_grok_fallback_expert_tp():
    m = mesh2()
    # grok: E=8 does NOT divide 16 -> experts replicated, d_model FSDP,
    # d_ff tensor-parallel
    assert spec_for("expert embed mlp", (8, 6144, 32768), m, pr(m)) == \
        P(None, "data", "model")


def test_granite_vocab_fallback():
    m = mesh2()
    # vocab 49155 odd -> shard the embed dim instead
    assert spec_for("vocab embed", (49155, 1024), m, pr(m)) == \
        P(None, "data")
    assert spec_for("vocab embed", (131072, 5120), m, pr(m)) == \
        P("model", "data")


def test_qwen25_heads_indivisible():
    m = mesh2()
    # 40 heads don't divide 16: heads replicated (the perf pathology
    # documented in EXPERIMENTS.md SPerf)
    assert spec_for("embed heads head_dim", (5120, 40, 128), m, pr(m)) == \
        P("data")


def test_dp_only_baseline_has_no_fsdp():
    m = mesh2()
    rules = pr(m, dp_only=True)
    assert spec_for("embed mlp", (4096, 14336), m, rules) == \
        P(None, "model")


def test_activation_batch_sharding():
    m2, m3 = mesh2(), mesh3()
    assert spec_for("batch seq embed", (256, 4096, 1024), m2, ar(m2)) == \
        P("data")
    assert spec_for("batch seq embed", (256, 4096, 1024), m3, ar(m3)) == \
        P(("pod", "data"))
    # batch=1 long-context: nothing divides -> replicated
    assert spec_for("batch seq embed", (1, 4096, 1024), m2, ar(m2)) == P()


def test_kv_cache_sequence_sharding():
    m = mesh2()
    assert spec_for(
        "batch cache_seq kv_heads head_dim", (128, 32768, 8, 128),
        m, ar(m),
    ) == P("data", "model")


def test_fsdp_over_pod_optin():
    m = mesh3()
    rules = pr(m, fsdp_over_pod=True)
    assert spec_for("embed mlp", (4096, 14336), m, rules) == \
        P(("pod", "data"), "model")
    # default: FSDP stays within pod
    assert spec_for("embed mlp", (4096, 14336), m, pr(m)) == \
        P("data", "model")


def test_no_axis_reuse_within_tensor():
    m = mesh2()
    # heads takes model; kv_heads must not reuse it
    s = spec_for("heads kv_heads", (16, 16), m, pr(m))
    assert s == P("model")


def test_rank_mismatch_raises():
    m = mesh2()
    with pytest.raises(ValueError):
        spec_for("embed mlp", (4, 4, 4), m, pr(m))


def test_expert_parallel_layout():
    """EP layout (sorted-dispatch a2a, core/ep.py) follows the rules
    engine's graceful-fallback discipline: None when the mesh has no
    model axis / size-1 axis / indivisible experts."""
    from repro.sharding.logical import expert_parallel_layout

    m2, m3 = mesh2(), mesh3()
    assert expert_parallel_layout(m2, 32) == \
        ("model", 16, ("data", "model"))
    assert expert_parallel_layout(m3, 64) == \
        ("model", 16, ("pod", "data", "model"))
    # grok: E=8 does not divide the 16-wide axis -> fallback (None)
    assert expert_parallel_layout(m2, 8) is None
    assert expert_parallel_layout(None, 32) is None
    data_only = AbstractMesh((16,), ("data",))
    assert expert_parallel_layout(data_only, 32) is None
    ep1 = AbstractMesh((16, 1), ("data", "model"))
    assert expert_parallel_layout(ep1, 32) is None
