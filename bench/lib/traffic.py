"""The one traffic generator: documents for packed training and open-loop
chat requests, from a mix's parameters and the run's seed.

Every seed gets the same sizes, order and arrival times: they come from
the mix's own ``lengths_seed``; only the token ids come from ``--seed``.
So two seeds do the same work and differ in what they compute, and a
seed changes no end-to-end metric by itself.

The lognormal document lengths copy data/dataset.py's calibration to
PackMamba's corpus (57-2048 tokens, mean 646).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(list(words)))


def lognormal_lengths(rng, n: int, mu: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    return np.clip(np.exp(rng.normal(mu, sigma, size=n)), lo,
                   hi).astype(np.int64)


class Corpus:
    """Documents for a packing loader: the interface of the program's
    ``SyntheticCorpus`` (``lengths``, ``sequence``, ``batch_of_sequences``,
    ``mean_length``), with step-indexed, pure draws. Each batch it hands
    out is also kept in ``handed_out[step]`` for the correctness check."""

    def __init__(self, docs: dict, seed: int, vocab: int):
        self.p = docs
        self.seed = seed
        self.vocab = vocab
        self.handed_out: Dict[int, List[np.ndarray]] = {}

    def lengths(self, step: int, n: int) -> np.ndarray:
        """The same lengths in the same order for every seed: the order
        decides which documents the packer fits into the rows, so it is
        part of the work a step does."""
        p = self.p
        return lognormal_lengths(_rng(p["lengths_seed"], step, n), n,
                                 p["mu"], p["sigma"], p["len_min"],
                                 p["len_max"])

    def sequence(self, step: int, idx: int, length: int) -> np.ndarray:
        return _rng(self.seed, 2, step, idx).integers(
            1, self.vocab, size=length, dtype=np.int32)

    def batch_of_sequences(self, step: int, n: int) -> List[np.ndarray]:
        seqs = [self.sequence(step, i, int(L))
                for i, L in enumerate(self.lengths(step, n))]
        self.handed_out[step] = seqs
        return seqs

    def mean_length(self, probe_steps: int = 50, per_step: int = 64) -> float:
        return float(np.mean(np.concatenate(
            [self.lengths(s, per_step) for s in range(probe_steps)])))


@dataclasses.dataclass
class Request:
    due_s: float              # offset from the window's start
    prompt: np.ndarray
    max_new: int


def chat_requests(mix: dict, seed: int, vocab: int, rate: float,
                  until_s: float) -> List[Request]:
    """Open-loop requests at ``rate`` per second, due from 0 to
    ``until_s``: one Poisson stream of arrivals and (prompt, answer)
    lengths fixed by the mix, the same for every seed and every window
    length; the prompt tokens come from ``seed``."""
    n = int(rate * until_s * 2) + 64
    due = np.cumsum(_rng(mix["lengths_seed"], 0).exponential(1.0 / rate,
                                                             size=n))
    assert due[-1] >= until_s, "the stream ends before until_s"
    pl, ol = mix["prompt"], mix["output"]
    plen = lognormal_lengths(_rng(mix["lengths_seed"], 1), n,
                             np.log(pl["median"]), pl["sigma"], pl["min"],
                             pl["max"])
    olen = lognormal_lengths(_rng(mix["lengths_seed"], 2), n,
                             np.log(ol["median"]), ol["sigma"], ol["min"],
                             ol["max"])
    r = _rng(seed, 3)
    return [Request(float(d), r.integers(1, vocab, size=int(p),
                                         dtype=np.int32), int(o))
            for d, p, o in zip(due, plen, olen) if d < until_s]
