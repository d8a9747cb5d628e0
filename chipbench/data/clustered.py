"""Clustered vectors (the paper's section 4.1, Fig. 4): the corpus of a
deployment and the query objects drawn from it.

The point law is copied from the repository's reproduction of the paper's
generator (trig-falloff clusters around random centres in [0,1]^dim, each
component drawn independently, so density ridges run parallel to the
axes), so that no change to the program's own data code can move this
yardstick.

The corpus is the deployment's dataset: it is drawn from the
configuration's ``data_seed`` and is the same in every run.  Queries are
objects drawn from the database, as in the paper's experiments, chosen by
the run's seed.
"""
from __future__ import annotations

import numpy as np


def corpus(cfg: dict) -> np.ndarray:
    """The ``n`` objects [n, dim] f32, from ``data_seed`` alone."""
    rng = np.random.default_rng(cfg["data_seed"])
    centres = rng.random((cfg["n_clusters"], cfg["dim"]))
    which = rng.integers(0, cfg["n_clusters"], size=cfg["n"])
    u = rng.random((cfg["n"], cfg["dim"]))
    offs = cfg["spread"] * np.sin(np.pi * (u - 0.5)) ** 3  # peaked at 0
    return np.clip(centres[which] + offs, 0.0, 1.0).astype(np.float32)


def queries(corpus: np.ndarray, seed: int, n: int,
            block: int = 0) -> np.ndarray:
    """``n`` query objects [n, dim] f32 drawn from the database; ``block``
    numbers successive draws of one run."""
    rng = np.random.default_rng([seed, 2, block])
    return corpus[rng.integers(0, len(corpus), size=n)]
