"""A run with the timed path broken underneath must come out not correct:
the look for a chip is skipped and the rest of a run is driven at a test
size on the CPU, held to the real cells' limits."""
import types

import jax
import jax.numpy as jnp
import pytest

from _bench_path import DATA, load, tiny, with_serving

import run
import train


def _measure(cell_name, mix_file, conf):
    bench = with_serving(run.load_bench())
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    args = types.SimpleNamespace(seed=2 ** 31 + 77, seconds=1.5, trace=0)
    return run.measure(bench, cell, conf, load(DATA / mix_file), args,
                       jax.devices(), {}, run.CompileClock())


def _train_with(monkeypatch, wrap):
    real = train.build_step

    def build(cfg, mix):
        opt, step = real(cfg, mix)
        return opt, wrap(step)

    monkeypatch.setattr(train, "build_step", build)
    return _measure("granite3b.finetune", "tiny_train.json",
                    tiny(expert_init="copy"))


def test_sound_training_run_is_correct():
    line = _measure("granite3b.finetune", "tiny_train.json",
                    tiny(expert_init="copy"))
    assert line["correct"] is True, line["checks"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    def wrap(step):
        def broken(state, batch, lr):
            _, mets = step(jax.tree.map(jnp.copy, state), batch, lr)
            return state, mets
        return broken

    line = _train_with(monkeypatch, wrap)
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_step_on_half_the_batch(monkeypatch):
    def wrap(step):
        def broken(state, batch, lr):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half, lr)
        return broken

    line = _train_with(monkeypatch, wrap)
    assert line["correct"] is False


def test_rows_sent_to_the_wrong_expert(monkeypatch):
    # the experts are exact copies, so the loss cannot see this; each
    # expert's gradient and change can
    from repro.core import moe

    real = moe.R.assignment_stream

    def shifted(r, E, g):
        tok, eid, w = real(r, E, g)
        return tok, jnp.where(eid < E, (eid + 1) % E, eid), w

    monkeypatch.setattr(moe.R, "assignment_stream", shifted)
    line = _measure("granite3b.finetune", "tiny_train.json",
                    tiny(expert_init="copy"))
    assert line["correct"] is False
    assert line["checks"]["loss_gap"]["value"] < 1e-4


def test_served_token_altered_where_it_is_produced(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._sample_one

    def altered(self, logits_row, seed0, rid, n):
        tok = real(self, logits_row, seed0, rid, n)
        return (tok + 1) % logits_row.shape[-1] if n == 2 else tok

    monkeypatch.setattr(ServeEngine, "_sample_one", altered)
    line = _measure("granite1b.chat", "tiny_chat.json", tiny())
    assert line["correct"] is False
