"""Plain exact reference: brute-force top-k in float64 numpy.

Independent of the program: it imports nothing from ``repro`` and reads
only the seeded corpus and queries.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1024  # queries per block, so the (block, N) matrix stays small


def distances(X: np.ndarray, Q: np.ndarray, metric: str) -> np.ndarray:
    """float64 (nq, n) distances under the engine's metric semantics:
    squared l2, or negative cosine similarity for ``cos``."""
    X = np.asarray(X, np.float64)
    Q = np.asarray(Q, np.float64)
    G = Q @ X.T
    if metric == "l2":
        D = (Q * Q).sum(-1)[:, None] + (X * X).sum(-1)[None, :] - 2.0 * G
        return np.maximum(D, 0.0)
    if metric == "cos":
        qn = np.linalg.norm(Q, axis=-1)
        xn = np.linalg.norm(X, axis=-1)
        return -G / (qn[:, None] * xn[None, :])
    raise ValueError(f"unknown metric {metric!r}")


def pair_distances(X: np.ndarray, Q: np.ndarray, ids: np.ndarray,
                   metric: str) -> np.ndarray:
    """float64 distance of ``X[ids[i, j]]`` to ``Q[i]``, computed
    directly (difference form for l2): ``(nq, k)``."""
    x = np.asarray(X, np.float64)[ids]
    q = np.asarray(Q, np.float64)[:, None, :]
    if metric == "l2":
        return ((x - q) ** 2).sum(-1)
    if metric == "cos":
        return -(x * q).sum(-1) / (np.linalg.norm(x, axis=-1)
                                   * np.linalg.norm(q, axis=-1))
    raise ValueError(f"unknown metric {metric!r}")


def exact_topk(X: np.ndarray, Q: np.ndarray, k: int, metric: str):
    """(ids, dists) of the exact top-k of every query, float64."""
    ids = np.empty((len(Q), k), np.int64)
    for lo in range(0, len(Q), BLOCK):
        D = distances(X, Q[lo:lo + BLOCK], metric)
        part = np.argpartition(D, k - 1, axis=1)[:, :k]
        order = np.take_along_axis(D, part, 1).argsort(axis=1, kind="stable")
        ids[lo:lo + BLOCK] = np.take_along_axis(part, order, 1)
    return ids, pair_distances(X, Q, ids, metric)
