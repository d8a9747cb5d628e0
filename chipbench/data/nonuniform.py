"""Non-uniform vectors (the paper's section 4.1, Fig. 9): the corpus of a
deployment and the query objects drawn from it.

The point law is copied from the repository's reproduction of the paper's
generator: each component is a polynomial transform of a uniform draw,
mirrored around 0.5, so mass gathers at the centre of [0,1]^dim and no
cluster structure helps the index prune.

The corpus comes from the configuration's ``data_seed`` alone; queries
are objects drawn from the database, chosen by the run's seed.
"""
from __future__ import annotations

import numpy as np


def corpus(cfg: dict) -> np.ndarray:
    """The ``n`` objects [n, dim] f32, from ``data_seed`` alone."""
    rng = np.random.default_rng(cfg["data_seed"])
    u = rng.random((cfg["n"], cfg["dim"]))
    x = 0.5 + 0.5 * np.sign(u - 0.5) * np.abs(2 * u - 1) ** cfg["power"]
    return x.astype(np.float32)


def queries(corpus: np.ndarray, seed: int, n: int,
            block: int = 0) -> np.ndarray:
    """``n`` query objects [n, dim] f32 drawn from the database; ``block``
    numbers successive draws of one run."""
    rng = np.random.default_rng([seed, 2, block])
    return corpus[rng.integers(0, len(corpus), size=n)]
