"""Seeded traffic: training batches.

One general generator per kind reads a traffic file's parameters. The
seed changes the content, never the amount of work: every seed gets the
same batch shapes.

The Markov text is a copy of ``repro.data.synthetic.SyntheticText``'s
bigram chain (a fixed random transition over hub tokens), kept here so
that the yardstick does not move with the program; it takes any whole
number as a seed.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.common import stable_seed


class MarkovText:
    """Order-1 chain over ``n_states`` hub tokens of the vocabulary."""

    def __init__(self, vocab: int, n_states: int, seed: int):
        rng = np.random.default_rng(stable_seed(seed, 1))
        k = min(n_states, vocab)
        self.hubs = rng.choice(vocab, size=k, replace=False).astype(np.int32)
        trans = rng.dirichlet(np.ones(k) * 0.3, size=k)
        self.cum = np.cumsum(trans, axis=1)
        self.start = rng.dirichlet(np.ones(k))
        self.seed = seed

    def rows(self, n_rows: int, length: int, index: int) -> np.ndarray:
        """(n_rows, length) tokens; ``index`` picks an independent draw."""
        rng = np.random.default_rng(stable_seed(self.seed, 2, index))
        k = len(self.hubs)
        states = np.empty((n_rows, length), np.int32)
        states[:, 0] = rng.choice(k, size=n_rows, p=self.start)
        u = rng.random((n_rows, length - 1))
        for t in range(length - 1):
            states[:, t + 1] = (self.cum[states[:, t]]
                                > u[:, t:t + 1]).argmax(axis=1)
        return self.hubs[states]


def train_batches(traffic: dict, vocab: int, seed: int, n: int
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``n`` distinct (tokens, labels) batches of the traffic's shape."""
    B, S = traffic["global_batch"], traffic["seq_len"]
    text = MarkovText(vocab, traffic["markov_states"], seed)
    out = []
    for i in range(n):
        toks = text.rows(B, S + 1, i)
        out.append((toks[:, :-1], toks[:, 1:]))
    return out
