"""Data pipeline: determinism, resume, host sharding, task structure."""
import numpy as np

from repro.configs import get_reduced
from repro.data import ClusteredBigramTask, lm_batch, make_iterator
from repro.data.synthetic import SUCCESSORS, frame_batch, patch_batch, \
    span_corruption_batch


def test_determinism_and_no_step_overlap():
    task = ClusteredBigramTask(vocab_size=256)
    b1 = lm_batch(task, 4, 32, step=3)
    b2 = lm_batch(task, 4, 32, step=3)
    b3 = lm_batch(task, 4, 32, step=4)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def _dense(task):
    """(K, V, V) transition matrices of the sparse tables."""
    succ, p = task.tables()
    K, V = task.n_clusters, task.vocab_size
    t = np.zeros((K, V, V))
    np.put_along_axis(t, succ, p, axis=-1)
    return t


def test_bigram_tables_are_stochastic_and_clustered():
    task = ClusteredBigramTask(vocab_size=64, n_clusters=4)
    succ, _ = task.tables()
    assert succ.shape == (4, 64, SUCCESSORS)
    # successors of a row are distinct in-vocab ids
    rows = succ.reshape(-1, SUCCESSORS)
    assert all(len(set(r)) == SUCCESSORS for r in rows)
    assert succ.min() >= 0 and succ.max() < 64
    t = _dense(task)
    np.testing.assert_allclose(t.sum(-1), 1.0, atol=1e-6)
    # clusters differ (the MoE-specializable structure)
    assert np.abs(t[0] - t[1]).max() > 0.1


def test_bigram_rows_match_dense_gumbel_top_k():
    """The direct draw has the distribution of the dense construction:
    top-SUCCESSORS of V iid Gumbel / concentration, renormalized. Mean
    sorted row probabilities over 16384 rows agree within 0.01 (their
    standard errors are below 0.003)."""
    task = ClusteredBigramTask(vocab_size=64, n_clusters=256)
    _, p = task.tables()
    rng = np.random.default_rng(0)
    g = rng.gumbel(size=(256 * 64, 64)) / task.concentration
    top = np.sort(g, axis=-1)[:, -SUCCESSORS:][:, ::-1]
    want = np.exp(top - top[:, :1])
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        p.reshape(-1, SUCCESSORS).mean(0), want.mean(0), atol=0.01
    )
    # every token is a successor about equally often (uniform ids)
    counts = np.bincount(task.tables()[0].ravel(), minlength=64)
    assert counts.min() > 0.8 * counts.mean()


def test_published_vocab_samples_quickly():
    """granite's 49155-token vocab: tables cost O(K * V), not O(K * V^2)."""
    task = ClusteredBigramTask(vocab_size=49155)
    toks = task.sample(2, 512, step=0)
    assert toks.shape == (2, 513)
    assert toks.min() >= 0 and toks.max() < 49155


def test_targets_are_next_tokens():
    task = ClusteredBigramTask(vocab_size=256)
    b = lm_batch(task, 2, 16, step=0)
    toks = task.sample(2, 16, 0)
    np.testing.assert_array_equal(b["tokens"], toks[:, :-1])
    np.testing.assert_array_equal(b["targets"], toks[:, 1:])


def test_host_sharding_partitions_batch():
    cfg = get_reduced("tinyllama-1.1b")
    its = [
        make_iterator(cfg, global_batch=8, seq_len=16, host_index=i,
                      host_count=2)
        for i in range(2)
    ]
    full = make_iterator(cfg, global_batch=8, seq_len=16, host_index=0,
                         host_count=1)
    got = [next(it)["tokens"] for it in its]
    want = next(full)["tokens"]
    np.testing.assert_array_equal(np.concatenate(got, 0), want)


def test_iterator_state_roundtrip():
    cfg = get_reduced("tinyllama-1.1b")
    it = make_iterator(cfg, global_batch=2, seq_len=16, host_index=0,
                       host_count=1)
    next(it), next(it)
    st = it.state()
    b3 = next(it)
    it2 = make_iterator(cfg, global_batch=2, seq_len=16, host_index=0,
                        host_count=1)
    it2.restore(st)
    b3b = next(it2)
    np.testing.assert_array_equal(b3["tokens"], b3b["tokens"])


def test_span_corruption_shapes():
    task = ClusteredBigramTask(vocab_size=256)
    b = span_corruption_batch(task, 2, 64, 24, step=1)
    assert b["enc_tokens"].shape == (2, 64)
    assert b["dec_tokens"].shape == (2, 24)
    assert b["targets"].shape == (2, 24)
    assert (b["targets"] == -1).any()  # padded positions masked
    # sentinels present in encoder stream
    assert (b["enc_tokens"] >= 256 - 32).any()


def test_patch_and_frame_batches():
    pb = patch_batch(4, 16, 32, 10, step=0)
    assert pb["patch_embeds"].shape == (4, 16, 32)
    assert pb["labels"].shape == (4,)
    assert pb["labels"].max() < 10
    task = ClusteredBigramTask(vocab_size=128)
    fb = frame_batch(task, 2, 32, 8, 64, step=0)
    assert fb["frames"].shape == (2, 32, 64)
    assert fb["dec_tokens"].shape == (2, 8)
