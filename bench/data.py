"""Inputs of every cell, generated from seeds by the benchmark itself.

These are the benchmark's own copies, so a later change to the program
cannot change the traffic:

- ``cluster_corpus`` draws users and items exactly as the program's
  ``data/synthetic.py:make_interactions`` does (same draws, same order),
  without the interactions; ``cluster_ids`` repeats its first draws, the
  cluster of every user and item;
- ``history_queries`` gives each user a history of the items it
  clicked, under ``make_interactions``'s click model, for a measure
  whose query is a history;
- ``poisson_offsets`` is the open-loop arrival schedule of
  ``serving/runtime.py:poisson_arrivals`` (exponential gaps), with the
  multiset of gaps fixed by the traffic file and only their order drawn
  from the run's seed, so every seed offers the same number of requests
  and the same gaps in another order.
"""
from __future__ import annotations

import numpy as np

# make_interactions's click probability off and on the user's cluster
CLICK = (0.15, 0.85)
# the stream of default_rng([data_seed, ...]) that draws histories
HISTORY_STREAM = 1


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


def cluster_ids(n_users: int, n_items: int, n_clusters: int, dim: int,
                seed: int):
    """(u_cl (n_users,), i_cl (n_items,)): the cluster of every user and
    item that ``cluster_corpus`` draws from the same arguments."""
    rng = np.random.default_rng(seed)
    rng.normal(size=(n_clusters, dim))                  # the centres
    return (rng.integers(0, n_clusters, n_users),
            rng.integers(0, n_clusters, n_items))


def history_queries(items: np.ndarray, n_users: int, n_clusters: int,
                    data_seed: int, length: int) -> np.ndarray:
    """(n_users, length * dim) float32: user u's row is the rows of
    ``items`` (``cluster_corpus``'s, from ``data_seed``) of u's last
    ``length`` clicks, concatenated oldest first.

    ``make_interactions`` draws each of u's interactions with an item
    uniform over all items and clicks it with probability
    ``CLICK[same cluster]``, so each of u's clicks, in order, is an
    independent draw with P(i) proportional to that probability: an item
    of u's own cluster with probability 0.85 n_own / (0.85 n_own + 0.15
    (n_items - n_own)), about 27% at 16 clusters, otherwise one of the
    rest, each uniformly. The draws come from ``default_rng([data_seed,
    HISTORY_STREAM])``."""
    n_items, dim = items.shape
    u_cl, i_cl = cluster_ids(n_users, n_items, n_clusters, dim, data_seed)
    by_cluster = np.argsort(i_cl, kind="stable")
    count = np.bincount(i_cl, minlength=n_clusters)
    n_own = count[u_cl][:, None]
    start = (np.cumsum(count) - count)[u_cl][:, None]
    off, on = CLICK
    p_own = on * n_own / (on * n_own + off * (n_items - n_own))
    rng = np.random.default_rng([data_seed, HISTORY_STREAM])
    own = rng.random((n_users, length)) < p_own
    pos = rng.integers(0, np.where(own, n_own, n_items - n_own))
    # positions in by_cluster: u's own block, or the rest with it skipped
    pos = np.where(own, start + pos, np.where(pos < start, pos, pos + n_own))
    return np.ascontiguousarray(
        items[by_cluster[pos]].reshape(n_users, length * dim), np.float32)


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
