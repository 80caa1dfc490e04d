"""Seeded corpora and query pools.

The clustered recipe is a copy of ``repro.data.synthetic.corpus_embeddings``
(64 centres, zipf-ish cluster weights, spread 0.35), kept here so that a
change to the program cannot change the benchmark's data.
"""

from __future__ import annotations

import numpy as np


def clustered(n: int, dim: int, rng: np.random.Generator,
              centers: np.ndarray, spread: float) -> np.ndarray:
    """``n`` points around ``centers`` with zipf-ish cluster weights."""
    w = 1.0 / np.arange(1, len(centers) + 1)
    assign = rng.choice(len(centers), size=n, p=w / w.sum())
    noise = rng.standard_normal((n, dim), dtype=np.float32)
    return (centers[assign] + spread * noise).astype(np.float32)


def make_data(gen: dict, n: int, dim: int, n_queries: int, seed: int):
    """(corpus (n, dim), query pool (n_queries, dim)), float32, from seed.

    ``gen["queries"]`` is ``"perturbed"`` (corpus points drawn uniformly,
    plus Gaussian noise of ``gen["query_noise"]``: RAG queries that land
    near documents) or ``"fresh"`` (held-out draws of the same clustered
    generator, as unseen words of an embedding vocabulary are).
    """
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((gen["clusters"], dim), dtype=np.float32)
    X = clustered(n, dim, rng, centers, gen["spread"])
    if gen["queries"] == "perturbed":
        pick = rng.choice(n, n_queries, replace=True)
        noise = rng.standard_normal((n_queries, dim), dtype=np.float32)
        Q = X[pick] + np.float32(gen["query_noise"]) * noise
    elif gen["queries"] == "fresh":
        Q = clustered(n_queries, dim, rng, centers, gen["spread"])
    else:
        raise ValueError(f"unknown query recipe {gen['queries']!r}")
    return X, Q.astype(np.float32)
