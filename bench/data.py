"""Inputs of every cell, generated from seeds by the benchmark itself.

These are the benchmark's own copies, so a later change to the program
cannot change the traffic:

- ``cluster_corpus`` draws users and items exactly as the program's
  ``data/synthetic.py:make_interactions`` does (same draws, same order),
  without the interactions;
- ``poisson_offsets`` is the open-loop arrival schedule of
  ``serving/runtime.py:poisson_arrivals`` (exponential gaps), with the
  multiset of gaps fixed by the traffic file and only their order drawn
  from the run's seed, so every seed offers the same number of requests
  and the same gaps in another order.
"""
from __future__ import annotations

import numpy as np


def cluster_corpus(n_users: int, n_items: int, n_clusters: int, dim: int,
                   seed: int):
    """(users (n_users, dim), items (n_items, dim)) float32: users and
    items share a latent cluster space, 0.5 * centre + 0.5 * noise."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32)
    u_cl = rng.integers(0, n_clusters, n_users)
    i_cl = rng.integers(0, n_clusters, n_items)
    users = (0.5 * centers[u_cl]
             + 0.5 * rng.normal(size=(n_users, dim))).astype(np.float32)
    items = (0.5 * centers[i_cl]
             + 0.5 * rng.normal(size=(n_items, dim))).astype(np.float32)
    return users, items


def poisson_offsets(rate: float, seconds: float, gap_seed: int,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (s, ascending, in [0, seconds)) of round(rate * seconds)
    requests. The exponential gaps come from ``gap_seed`` and are scaled
    to fill the window exactly; ``rng`` only permutes them."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(gap_seed).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
