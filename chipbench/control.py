"""The control that ``correct`` must refuse.

:class:`Bf16BruteForce` is the reference put in the engine's place and
computed in bfloat16, the precision below the float32 that the
configurations state (the program has no bfloat16 path of its own). Its
ids are the exact top-k under bfloat16 rounding, so its recall stays
high; its distances carry bfloat16 error.

The benchmark's own runs never run it: ``chipbench/calibrate.py`` reads
it on the chip, and ``tests/test_correctness.py`` at a small size.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class _Result:
    ids: np.ndarray
    dists: np.ndarray
    stats: list


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _bf16_topk(Q, X, k: int, metric: str):
    Q, X = Q.astype(jnp.bfloat16), X.astype(jnp.bfloat16)
    G = Q @ X.T
    if metric == "l2":
        D = (Q * Q).sum(-1)[:, None] + (X * X).sum(-1)[None, :] - 2 * G
    else:  # cos
        qn = jnp.linalg.norm(Q, axis=-1)
        xn = jnp.linalg.norm(X, axis=-1)
        D = -G / (qn[:, None] * xn[None, :])
    neg, ids = jax.lax.top_k(-D, k)
    return ids, (-neg).astype(jnp.float32)


class Bf16BruteForce:
    """Exact search computed in bfloat16, with the engine's ``search``."""

    def __init__(self, X: np.ndarray, metric: str):
        self.X = jnp.asarray(X)
        self.metric = metric

    def search(self, request):
        q = np.asarray(request.query, np.float32)
        ids, dists = _bf16_topk(jnp.atleast_2d(q), self.X, request.k,
                                self.metric)
        ids, dists = np.asarray(ids), np.asarray(dists)
        if q.ndim == 1:
            ids, dists = ids[0], dists[0]
        return _Result(ids=ids, dists=dists, stats=[])

    # the engine's other calls in a run: there is no tiered store here
    def warm_cache(self) -> None:
        pass

    def snapshot_access_stats(self) -> dict:
        return {"n_db": 0, "items_fetched": 0}

    def cache_bytes(self) -> int:
        return 0


def bf16_brute_force(X, graph, config, capacity, traced):
    """``make_engine`` of the bfloat16 reference control."""
    return Bf16BruteForce(X, config["metric"])

