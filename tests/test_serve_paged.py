"""Continuous-batching serve engine: scheduler/block-pool accounting,
staggered-admission identity, streaming, EOS, bf16 cache parity, and
live-token MoE decode masking."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import model_zoo as zoo
from repro.models import param as pm
from repro.serve import (
    BlockPool,
    Request,
    Scheduler,
    ServeConfig,
    ServeEngine,
    blocks_needed,
)


def _dropless(cfg):
    """Decode-grade MoE config: capacity can't couple a token's routing
    to its batch, so continuous batching is output-identical to solo
    runs (see repro/serve/engine.py docstring)."""
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)
        )
    )


@pytest.fixture(scope="module")
def granite():
    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    return cfg, vals


@pytest.fixture(scope="module")
def paged_engine(granite):
    cfg, vals = granite
    return ServeEngine(
        vals, cfg,
        ServeConfig(max_batch=3, max_len=64, paged=True, block_size=8),
    )


# ---------------------------------------------------------------------------
# host-side accounting (no jax)
# ---------------------------------------------------------------------------


def test_block_pool_accounting():
    pool = BlockPool(6, 8)
    assert pool.capacity == 5 and pool.num_free == 5
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a
    assert pool.alloc(3) is None  # atomic: 2 left, no partial grab
    b = pool.alloc(2)
    assert pool.num_free == 0
    pool.free(a)
    assert pool.num_free == 3
    with pytest.raises(ValueError, match="double free"):
        pool.free(a)
    pool.free(b)
    assert pool.num_free == pool.capacity


def test_blocks_needed_covers_bucketed_prefill():
    # prompt 9 buckets to 16 (2 blocks of 8); budget extends past it
    assert blocks_needed(9, 1, 8) == 2
    assert blocks_needed(9, 8, 8) == 3  # 9 + 8 = 17 -> 3 blocks
    assert blocks_needed(8, 0, 8) == 1


def test_scheduler_fcfs_admission_and_eviction():
    pool = BlockPool(1 + 4, 8)
    sched = Scheduler(2, pool, max_len=64)
    # r0/r1 fill both slots; r2 queues; r3 behind it (strict FCFS)
    for rid, plen, new in [(0, 8, 8), (1, 8, 8), (2, 8, 8), (3, 1, 1)]:
        sched.submit(Request(rid=rid, prompt=[1] * plen, max_new=new))
    admitted = sched.admit(0)
    assert [s.request.rid for s in admitted] == [0, 1]
    assert sched.admit(0) == []  # no slot free
    sched.finish(admitted[0], 3, "budget")
    # slot free but r2 needs 2 blocks and only r0's 2 came back -> admit
    nxt = sched.admit(3)
    assert [s.request.rid for s in nxt] == [2]
    # r3 (1 block) must NOT overtake while blocks are short... here
    # blocks remain, but only one slot: r3 waits on slots, not order.
    assert sched.admit(3) == []
    assert sched.has_work
    assert sched.finished[0]["reason"] == "budget"


def test_scheduler_admits_in_arrival_order():
    """FCFS means ARRIVAL order: an early-arriving request submitted
    late must not starve behind a late-arriving one submitted first."""
    pool = BlockPool(1 + 8, 8)
    sched = Scheduler(1, pool, max_len=64)
    sched.submit(Request(rid=0, prompt=[1], max_new=1, arrival=10))
    sched.submit(Request(rid=1, prompt=[1], max_new=1, arrival=0))
    assert sched.next_arrival() == 0
    admitted = sched.admit(0)
    assert [s.request.rid for s in admitted] == [1]


def test_scheduler_rejects_duplicate_rid_and_zero_budget():
    pool = BlockPool(1 + 8, 8)
    sched = Scheduler(2, pool, max_len=64)
    sched.submit(Request(rid=0, prompt=[1, 2], max_new=4))
    with pytest.raises(ValueError, match="duplicate request id"):
        sched.submit(Request(rid=0, prompt=[3], max_new=4))
    with pytest.raises(ValueError, match="max_new"):
        sched.submit(Request(rid=1, prompt=[1], max_new=0))


def test_scheduler_rejects_oversized_requests():
    pool = BlockPool(3, 8)  # capacity 2 -> 16 tokens
    sched = Scheduler(1, pool, max_len=256)
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit(Request(rid=0, prompt=[1] * 20, max_new=20))
    with pytest.raises(ValueError, match="prompt"):
        Scheduler(1, pool, max_len=8).submit(
            Request(rid=1, prompt=[1] * 8, max_new=1)
        )


# ---------------------------------------------------------------------------
# engine-level identities
# ---------------------------------------------------------------------------


def test_paged_matches_static_engine_greedy(granite):
    """Same-length prompts (the static engine's right-padding is exact
    there): paged continuous batching must reproduce the static batch
    token-for-token under greedy decoding."""
    cfg, vals = granite
    static = ServeEngine(vals, cfg, ServeConfig(max_batch=3, max_len=64))
    paged = ServeEngine(
        vals, cfg,
        ServeConfig(max_batch=3, max_len=64, paged=True, block_size=8),
    )
    prompts = [[5, 6, 7, 8], [9, 10, 11, 12], [1, 2, 3, 4]]
    assert static.generate(prompts, max_new=6) == paged.generate(
        prompts, max_new=6
    )


def test_dense_arch_paged_matches_static():
    cfg = get_reduced("tinyllama-1.1b")
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    static = ServeEngine(vals, cfg, ServeConfig(max_batch=2, max_len=64))
    paged = ServeEngine(
        vals, cfg,
        ServeConfig(max_batch=2, max_len=64, paged=True, block_size=8),
    )
    prompts = [[3, 1, 4, 1], [2, 7, 1, 8]]
    assert static.generate(prompts, max_new=5) == paged.generate(
        prompts, max_new=5
    )


def test_staggered_admission_matches_solo_runs(paged_engine):
    """The acceptance identity: mid-flight admissions and evictions must
    not perturb any other request — every staggered continuation equals
    the same request served alone."""
    reqs = [
        Request(rid=0, prompt=[5, 6, 7], max_new=5),
        Request(rid=1, prompt=[9, 10, 11, 12, 13], max_new=8, arrival=2),
        Request(rid=2, prompt=[1, 2], max_new=3, arrival=4),
    ]
    outs, stats = paged_engine.serve(reqs)
    for r in reqs:
        solo, _ = paged_engine.serve(
            [Request(rid=r.rid, prompt=list(r.prompt),
                     max_new=r.max_new)]
        )
        assert outs[r.rid] == solo[r.rid], f"rid {r.rid} diverged"
    # later arrivals really were admitted mid-flight
    assert stats[1]["admitted_at"] == 2
    assert stats[2]["admitted_at"] == 4


def test_eviction_admits_queued_request_midflight(granite):
    """With one slot, the second request must be admitted exactly when
    the first finishes — continuous batching, not batch barriers."""
    cfg, vals = granite
    eng = ServeEngine(
        vals, cfg,
        ServeConfig(max_batch=1, max_len=64, paged=True, block_size=8),
    )
    reqs = [
        Request(rid=0, prompt=[4, 5], max_new=4),
        Request(rid=1, prompt=[6, 7], max_new=3),
    ]
    outs, stats = eng.serve(reqs)
    assert stats[0]["reason"] == "budget"
    assert stats[1]["admitted_at"] >= stats[0]["finished_at"]
    solo, _ = eng.serve([Request(rid=1, prompt=[6, 7], max_new=3)])
    assert outs[1] == solo[1]


def test_streaming_and_eos(paged_engine):
    # learn a token the model actually produces, then use it as EOS
    base, _ = paged_engine.serve(
        [Request(rid=0, prompt=[4, 5, 6], max_new=6)]
    )
    gen = base[0][3:]
    eos = gen[1]  # second generated token
    # Greedy output repeats tokens, so EOS may fire earlier: the engine
    # stops at the FIRST emitted occurrence (included).
    cut = gen.index(eos) + 1
    got = []
    outs, stats = paged_engine.serve(
        [Request(rid=0, prompt=[4, 5, 6], max_new=6, eos_id=eos)],
        on_token=lambda rid, t: got.append((rid, t)),
    )
    assert stats[0]["reason"] == "eos"
    assert outs[0] == base[0][:3 + cut]  # truncated at (and incl.) EOS
    assert [t for _, t in got] == outs[0][3:]  # streamed == emitted


def test_temperature_sampling_slot_independent(paged_engine):
    """Temperature sampling folds rng on (rid, token index) — solo and
    staggered runs draw identical samples."""
    eng = ServeEngine(
        paged_engine.params, paged_engine.cfg,
        ServeConfig(max_batch=3, max_len=64, paged=True, block_size=8,
                    temperature=0.8),
    )
    rng = jax.random.PRNGKey(7)
    reqs = [
        Request(rid=0, prompt=[5, 6], max_new=4),
        Request(rid=1, prompt=[8, 9, 10], max_new=4, arrival=1),
    ]
    outs, _ = eng.serve(reqs, rng=rng)
    for r in reqs:
        solo, _ = eng.serve(
            [Request(rid=r.rid, prompt=list(r.prompt),
                     max_new=r.max_new)],
            rng=rng,
        )
        assert outs[r.rid] == solo[r.rid]


def test_bf16_cache_parity(granite):
    """cache_dtype plumbs end-to-end in both engines: bf16 KV caches
    stay within tolerance of f32 on the first decode logits and agree on
    the greedy token."""
    cfg, vals = granite
    for paged in (False, True):
        lgs = {}
        for cd in ("float32", "bfloat16"):
            eng = ServeEngine(
                vals, cfg,
                ServeConfig(max_batch=1, max_len=64, paged=paged,
                            block_size=8, cache_dtype=cd),
            )
            assert eng._cache_dtype == (
                jnp.bfloat16 if cd == "bfloat16" else jnp.float32
            )
            out = eng.generate([[5, 6, 7, 8]], max_new=2)
            lgs[cd] = out[0]
        # greedy continuations from bf16 vs f32 caches agree on these
        # short horizons (logit gaps >> bf16 cache rounding)
        assert lgs["float32"] == lgs["bfloat16"], f"paged={paged}"


def test_paged_cache_dtype_reaches_pool(granite):
    cfg, _ = granite
    cache = zoo.init_paged_serve_cache(cfg, 4, 8, dtype=jnp.bfloat16)
    leaves = jax.tree.leaves(cache)
    assert leaves and all(l.dtype == jnp.bfloat16 for l in leaves)


def test_paged_rejects_non_attention_stacks():
    cfg = get_reduced("rwkv6-7b")
    with pytest.raises(ValueError, match="attention-only|decoder-only"):
        zoo.init_paged_serve_cache(cfg, 4, 8)
    cfg = get_reduced("whisper-base")
    with pytest.raises(ValueError, match="decoder-only"):
        zoo.init_paged_serve_cache(cfg, 4, 8)


# ---------------------------------------------------------------------------
# live-token MoE decode (token_mask plumbing)
# ---------------------------------------------------------------------------


def test_moe_token_mask_drops_dead_tokens():
    """Masked (free-slot) tokens claim no experts and produce zero
    output; live tokens are bit-identical to the unmasked call under a
    dropless capacity (same group composition)."""
    from repro.configs import MoECfg
    from repro.core.moe import moe_apply, moe_init

    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    moe = cfg.moe
    params = moe_init(jax.random.PRNGKey(0), cfg, moe)
    vals, _ = pm.split(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 1, cfg.d_model))
    mask = jnp.asarray([1, 0, 1, 1, 0, 1], bool)[:, None]
    for block in (8, 128):
        y_all, _ = moe_apply(
            vals, x, cfg, moe, router_kind="top_k", sorted_block=block
        )
        y_m, mets = moe_apply(
            vals, x, cfg, moe, router_kind="top_k", sorted_block=block,
            token_mask=mask,
        )
        np.testing.assert_allclose(
            np.asarray(y_m[mask[:, 0]]),
            np.asarray(y_all[mask[:, 0]]),
            atol=1e-6, rtol=1e-6, err_msg=f"block {block}",
        )
        assert float(jnp.abs(y_m[~mask[:, 0]]).max()) == 0.0, block
        # metrics normalize over live tokens: dropless => 0 dropped,
        # even with 2 of 6 slots dead
        assert float(mets["dropped_frac"]) == 0.0, block


def test_moe_token_mask_shrinks_grouped_rows():
    """The sorted dispatch's ragged buffer holds zero assignments for
    masked tokens — the 'expert compute scales with live tokens' claim
    at the routing level."""
    from repro.core import routing as R

    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    moe = cfg.moe
    G, g, E = 1, 8, moe.num_experts
    logits = jax.random.normal(jax.random.PRNGKey(0), (G, g, E))
    mask = jnp.asarray([[1, 1, 0, 0, 0, 0, 0, 1]], bool)
    r = R.route(logits, moe, "top_k", token_mask=mask)
    tok, eid, w = R.assignment_stream(r, E, g)
    live_assignments = int((eid < E).sum())
    assert live_assignments == int(mask.sum()) * moe.top_k
    # EC refuses the mask (decoders never route EC)
    with pytest.raises(ValueError, match="token-choice"):
        R.route(logits, moe, "expert_choice", token_mask=mask)
