"""The one traffic generator: a mix file of parameters -> inputs.

Serving mixes (``"kind": "serve"``) give requests: when each is due, its
prompt and its output length. Training mixes (``"kind": "train"``) give
batches of token rows. Everything is drawn from ``--seed``.

Variance control: every seed gets the SAME multiset of lengths and
inter-arrival gaps, in another order. Lengths are the distribution's
quantiles at the midpoints of ``n`` equal strata and gaps the
exponential's, each set permuted by the seed; the window's requests and
the ones sent while the window drains are drawn as two separate sets, so
the window always carries the same work. The seed changes the order and
the token ids, not how much there is to do.

Serve mix keys:

* ``loop``: ``"open"``, the only loop so far: ``rate_rps`` requests per
  second with Poisson gaps, sent whether or not the server keeps up;
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}``
  lognormal lengths in tokens;
* ``tokens``: ``{"zipf_s"}`` token ids drawn from a Zipf law over the
  vocabulary (a fixed, seed-independent rank -> id shuffle);
* ``drain_s``: how long after the window closes the run waits for the
  window's requests to finish (arrivals go on meanwhile).
"""
from __future__ import annotations

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int, rng) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf(q) for q in strata(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def exp_gaps(rate: float, n: int, rng) -> np.ndarray:
    return rng.permutation(-np.log1p(-strata(n)) / rate)


class ZipfTokens:
    """Token ids from a Zipf law over ``vocab`` ids; rank r -> id by a
    fixed shuffle, so the frequent ids are not the low ones."""

    def __init__(self, vocab: int, s: float):
        p = 1.0 / np.arange(1, vocab + 1) ** s
        self.cdf = np.cumsum(p / p.sum())
        self.ids = np.random.default_rng(0).permutation(vocab)

    def draw(self, rng, shape) -> np.ndarray:
        u = rng.random(shape)
        r = np.minimum(np.searchsorted(self.cdf, u), len(self.ids) - 1)
        return self.ids[r].astype(np.int32)


def _arrivals(rate, n_win, n_after, seconds, rng):
    """Due times (s): ``n_win`` spread over [0, seconds) -- stretched so
    the last one falls half a gap before the window closes -- then
    ``n_after`` more from ``seconds`` on."""
    win = np.cumsum(exp_gaps(rate, n_win, rng))
    win *= seconds * (1 - 0.5 / n_win) / win[-1]
    after = seconds + np.cumsum(exp_gaps(rate, n_after, rng))
    return np.concatenate([win, after])


def serve_requests(spec: dict, seed: int, seconds: float,
                   vocab: int) -> list[dict]:
    """Requests ``{"rid", "due", "prompt", "max_new", "window"}``;
    ``window`` marks the requests due inside the window."""
    if spec["loop"] != "open":
        raise ValueError(f"unknown loop {spec['loop']!r}")
    rng = np.random.default_rng(seed)
    toks = ZipfTokens(vocab, spec["tokens"]["zipf_s"])
    n_win = int(round(spec["rate_rps"] * seconds))
    n_after = int(np.ceil(spec["rate_rps"] * spec["drain_s"]))
    sizes = [n_win, n_after]
    plens = np.concatenate([lognormal_lengths(spec["prompt"], n, rng)
                            for n in sizes])
    olens = np.concatenate([lognormal_lengths(spec["output"], n, rng)
                            for n in sizes])
    due = _arrivals(spec["rate_rps"], n_win, n_after, seconds, rng)
    return [{"rid": i, "due": float(due[i]),
             "prompt": toks.draw(rng, int(plens[i])),
             "max_new": int(olens[i]), "window": i < n_win}
            for i in range(len(plens))]


def train_batch(spec: dict, seed: int, step: int, vocab: int,
                toks: ZipfTokens | None = None) -> dict:
    """Batch ``step`` of a training mix: ``batch`` rows of ``seq_len``
    Zipf-drawn tokens and their next-token targets, every row new."""
    toks = toks or ZipfTokens(vocab, spec["tokens"]["zipf_s"])
    rng = np.random.default_rng([seed, step])
    rows = toks.draw(rng, (spec["batch"], spec["seq_len"] + 1))
    return {"tokens": rows[:, :-1], "targets": rows[:, 1:]}
