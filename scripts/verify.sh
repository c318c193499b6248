#!/usr/bin/env bash
# One-command local gate: tier-1 tests + kernel micro-bench smoke.
#
#   scripts/verify.sh [extra pytest args]
#
# Runs the ROADMAP tier-1 command (PYTHONPATH=src python -m pytest -x -q)
# and then the kernel micro-benchmarks in smoke mode (REPRO_BENCH_SMOKE=1,
# reduced shapes but the same code paths, including the Pallas custom-VJP
# backward kernels in interpret mode) so perf-path regressions fail here
# before they reach a TPU.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "[verify] tier-1: python -m pytest -x -q $*"
python -m pytest -x -q "$@"

echo "[verify] dispatch parity on a forced 8-device CPU mesh"
# The expert-parallel sorted dispatch (moe.ep="a2a", shard_map ragged
# all-to-all) needs real multiple devices to exercise its collectives:
# force 8 CPU devices and run the parity suite (sorted-EP vs
# single-device sorted vs gather, outputs + grads, all routers, empty
# local experts). The module self-skips in the 1-device tier-1 run.
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
  python -m pytest -x -q tests/test_ep_dispatch.py

echo "[verify] chaos lane: fault-injection sweep (REPRO_CHAOS=1, wider seeds)"
# tests/test_serve_chaos.py runs in tier-1 above with a small seed
# sweep; REPRO_CHAOS=1 widens the seeded fault-injection sweep (random
# evictions, pool-exhaustion holds, admission bursts, deadline storms —
# pool invariants audited every tick, greedy parity vs a clean run) so
# every verify exercises the robustness layer harder than CI-minimum.
REPRO_CHAOS=1 python -m pytest -x -q tests/test_serve_chaos.py

echo "[verify] spec lane: speculative parity sweep (REPRO_SPEC=1, wider seeds)"
# tests/test_speculative.py runs in tier-1 above with one rng per
# parity check; REPRO_SPEC=1 widens the acceptance/identity seed sweep
# (greedy spec == greedy vanilla for every draft kind, the q == p
# rejection-sampling identity at temperature on an upcycled
# checkpoint, chaos + speculation keeping pool invariants green) the
# way REPRO_CHAOS widens the fault-injection sweep.
REPRO_SPEC=1 python -m pytest -x -q tests/test_speculative.py

echo "[verify] fleet lane: multi-engine chaos sweep (REPRO_FLEET=1, wider seeds)"
# tests/test_fleet.py runs in tier-1 above with a 2-seed chaos sweep;
# REPRO_FLEET=1 widens the fleet-level fault-injection sweep (seeded
# engine kills mid-decode, heartbeat loss, slow-engine degradation —
# every request must reach exactly ONE fleet-terminal status, migrated
# greedy completions stay token-identical to the unchaosed solo run,
# and every surviving pool passes its per-tick invariant audit).
REPRO_FLEET=1 python -m pytest -x -q tests/test_fleet.py

echo "[verify] train-chaos lane: self-healing trainer sweep (REPRO_TRAIN_CHAOS=1, wider seeds)"
# tests/test_train_chaos.py runs in tier-1 above with a small seed
# sweep; REPRO_TRAIN_CHAOS=1 widens the train-side fault-injection
# sweep (injected loss spikes -> rollback + batch-window skip,
# mid-run crashes -> bit-exact resume, preemption storms, transient +
# corrupt checkpoint-store IO — trainer invariants audited every
# step, deterministic_rows() bit-identical across replays).
REPRO_TRAIN_CHAOS=1 python -m pytest -x -q tests/test_train_chaos.py

echo "[verify] obs lane: JSONL-sink smoke serve + metric schema lint"
# Runs a solo chunked serve, a 2-replica autoscaling fleet, and a
# checkpoint-retry fault through a real JsonlSink, then cross-checks
# every emitted metric name / row field against the reference doc
# (src/repro/obs/README.md) — an undocumented emission fails verify,
# so the metrics reference can never silently drift from the code.
python -m repro.obs.lint

echo "[verify] kernel micro-bench + serving bench + roofline (smoke mode)"
# kernels_micro exercises every ops.* implementation (including the
# Pallas custom-VJP kernels in interpret mode, the grouped-GEMM expert
# FFN forward and backward, the compacted block walk's dead-block
# byte-savings row, and the chunked paged prefill vs per-token
# decode-walk comparison); serve_bench runs the
# continuous-batching vs static-batch comparison under a Poisson
# arrival trace PLUS the long-prompt bursty scenario comparing static /
# prefill-on-join / chunked-mixed-step admission (wall-clock TTFT,
# decode stalls, prefix-cache hit rate) AND the overload scenario
# (~2x sustainable arrival rate, shedding + TTFT deadlines) AND the
# speculative scenario (--draft none vs dense vs top1 on an upcycled
# checkpoint: the dense parent drafts at ~1.0 acceptance and must beat
# vanilla decode tokens/s by >= 1.3x at smoke scale, >= 2x full) AND
# the fleet scenario (1 engine vs 3 replicas with one killed
# mid-trace: completed-request ratio must hold and p99 TTFT stays
# bounded through the failover) that
# writes the BENCH_serve.json perf-trajectory artifact; the paged
# serve subsystem's tests themselves — tests/test_paged_decode.py,
# test_paged_prefill.py, test_serve_paged.py, test_serve_chunked.py,
# test_serve_chaos.py — run in the tier-1 pytest above; roofline
# keeps the static per-kernel FLOP/byte models —
# ragged-bytes ratios, paged-vs-dense decode bytes, paged-prefill
# chunk-vs-decode-walk bytes, the EP-a2a vs weight-gather comm
# crossover — importable and consistent.
REPRO_BENCH_SMOKE=1 PYTHONPATH="$PYTHONPATH:." \
  python -m benchmarks.run --only kernels_micro,serve_bench,roofline

echo "[verify] OK"
