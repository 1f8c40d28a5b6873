"""The participation gate of a round, recomputed plainly from the seed.

The guarded cell's gate is a pure function of the seeded latency graph: the
cell's file states it (``gate``), and this module follows that statement
with nothing of the program: bandwidths drawn from ``--seed`` by numpy's
``default_rng(seed).uniform`` over an [n, n] matrix, a directed edge's
weight the inverse of its bandwidth, weighted PageRank as networkx defines
it (here the stationary vector from one linear solve, not a power
iteration), and a client masked when its rank lies more than ``band_sigma``
population standard deviations from the mean, unless it is the protected
source. A cell whose file states no gate keeps every client in every round.
"""

from __future__ import annotations

import numpy as np


def bandwidths(n, seed, low, high):
    bw = np.random.default_rng(int(seed)).uniform(low, high, size=(n, n))
    np.fill_diagonal(bw, 0.0)
    return bw


def pagerank(weights, damping):
    """Stationary ranks of the walk that follows an edge in proportion to its
    weight with probability ``damping`` and jumps anywhere otherwise."""
    n = weights.shape[0]
    out = weights.sum(axis=1)
    P = np.full((n, n), 1.0 / n)  # a node with no way out goes anywhere
    rows = out > 0
    P[rows] = weights[rows] / out[rows, None]
    # r = (1 - d) / n + d * r P, solved for r
    r = np.linalg.solve((np.eye(n) - damping * P).T, np.full((n,), (1.0 - damping) / n))
    return r / r.sum()


def expected_mask(gate, n, seed):
    """The 0/1 participation mask every round of a sound run has."""
    if not gate:
        return np.ones((n,), np.float64)
    if gate["filter"] != "pagerank":
        raise ValueError(f"no plain reference for the gate {gate['filter']!r}")
    bw = bandwidths(n, seed, *gate["bandwidth_mbps"])
    w = np.where(bw > 0, 1.0 / np.where(bw > 0, bw, 1.0), 0.0)
    r = pagerank(w, gate["damping"])
    far = np.abs(r - r.mean()) > gate["band_sigma"] * r.std()
    far[gate["protected_client"] % n] = False
    return np.where(far, 0.0, 1.0)
