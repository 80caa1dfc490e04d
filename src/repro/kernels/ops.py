"""Jitted public wrappers over the Pallas kernels, with backend dispatch.

On TPU (the target) these route to the compiled Pallas kernels. On any
other backend Pallas can only *interpret* — correct but slow to compile
at production grids — so the mathematically-identical jnp reference
path runs instead, keeping the multi-pod dry-run's HLO clean and compile
times sane. Kernel-vs-ref equivalence is enforced by the sweep tests in
``tests/test_kernels.py`` (interpret mode), and the kernels' TPU
lowering by ``tests/test_tpu_compile.py``.

Set ``REPRO_FORCE_PALLAS=1`` to run the interpret-mode kernels off-TPU.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.adc_gather_distance import (
    adc_gather_distance_batch_pallas,
    adc_gather_distance_pallas,
)
from repro.kernels.dequant_gather_distance import (
    dequant_gather_distance_batch_pallas,
    dequant_gather_distance_pallas,
)
from repro.kernels.distance import distance_matrix_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.gather_distance import (
    gather_distance_batch_pallas,
    gather_distance_pallas,
)
from repro.kernels.topk import merge_topk_pallas, topk_pallas


def _pallas_mode():
    """``None`` routes to the jnp reference; otherwise the ``interpret``
    flag for the Pallas kernel (False on TPU: compiled)."""
    if jax.default_backend() == "tpu":
        return False
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return None


def distance_matrix(Q: jnp.ndarray, X: jnp.ndarray, metric: str = "l2"):
    """(B, d) × (N, d) → (B, N) f32 distances."""
    interp = _pallas_mode()
    if interp is not None:
        return distance_matrix_pallas(Q, X, metric=metric, interpret=interp)
    return ref.distance_matrix_ref(Q, X, metric)


def distance_topk_ready(Q, X, metric: str = "l2"):
    """Distance matrix shaped for a follow-up top-k (distributed scan)."""
    return distance_matrix(Q, X, metric)


def topk(D: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    interp = _pallas_mode()
    if interp is not None:
        return topk_pallas(D, k, interpret=interp)
    return ref.topk_ref(D, k)


def merge_topk(dists: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Fused cross-shard top-k merge: dedup duplicate ids (same node
    surfacing from several shards), drop sentinels (id < 0 / non-finite
    dist), return the k smallest as (dists, ids, src) with beam_merge's
    lowest-input-position tie break (DESIGN.md §10)."""
    interp = _pallas_mode()
    if interp is not None:
        return merge_topk_pallas(dists, ids, k, interpret=interp)
    return ref.merge_topk_ref(dists, ids, k)


def distance_topk(Q, X, k: int, metric: str = "l2"):
    """Fused scan: distance matrix + split-K top-k."""
    return topk(distance_matrix(Q, X, metric), k)


def gather_distance(table, ids, q, metric: str = "l2"):
    interp = _pallas_mode()
    if interp is not None:
        return gather_distance_pallas(table, ids, q, metric=metric,
                                      interpret=interp)
    return ref.gather_distance_ref(table, ids, q, metric)


def gather_distance_batch(table, ids, Q, metric: str = "l2"):
    """(B, K) ids × (B, d) queries → (B, K) distances (batched lazy load)."""
    interp = _pallas_mode()
    if interp is not None:
        return gather_distance_batch_pallas(table, ids, Q, metric=metric,
                                            interpret=interp)
    return ref.gather_distance_batch_ref(table, ids, Q, metric)


def dequant_gather_distance(table, scales, ids, q, metric: str = "l2"):
    """Quantized-table fused gather + distance: (N, d) int8/f16 payload
    with (N,) per-row scales → (B,) f32 distances (DESIGN.md §7)."""
    interp = _pallas_mode()
    if interp is not None:
        return dequant_gather_distance_pallas(
            table, scales, ids, q, metric=metric, interpret=interp)
    return ref.dequant_gather_distance_ref(table, scales, ids, q, metric)


def dequant_gather_distance_batch(table, scales, ids, Q, metric: str = "l2"):
    """Batched quantized-table fused gather + distance: (B, K) ids ×
    (B, d) queries → (B, K) f32 distances (batched lazy load, §7)."""
    interp = _pallas_mode()
    if interp is not None:
        return dequant_gather_distance_batch_pallas(
            table, scales, ids, Q, metric=metric, interpret=interp)
    return ref.dequant_gather_distance_batch_ref(table, scales, ids, Q,
                                                 metric)


def adc_gather_distance(codes, lut, ids, metric: str = "l2"):
    """PQ-coded fused code-gather + LUT-accumulate (ADC): (N, M) uint8
    codes × an (L, M, 256) per-query table → (B,) f32 distances
    (DESIGN.md §12). Build the table with ``repro.core.pq.build_lut_*``."""
    interp = _pallas_mode()
    if interp is not None:
        return adc_gather_distance_pallas(
            codes, lut, ids, metric=metric, interpret=interp)
    return ref.adc_gather_distance_ref(codes, lut, ids, metric)


def adc_gather_distance_batch(codes, luts, ids, metric: str = "l2"):
    """Batched ADC: (B, K) ids × (B, L, M, 256) per-query tables →
    (B, K) f32 distances (batched lazy load, §12)."""
    interp = _pallas_mode()
    if interp is not None:
        return adc_gather_distance_batch_pallas(
            codes, luts, ids, metric=metric, interpret=interp)
    return ref.adc_gather_distance_batch_ref(codes, luts, ids, metric)


def embedding_bag(table, idx, weights=None, combiner: str = "sum"):
    interp = _pallas_mode()
    if interp is not None and weights is None:
        return embedding_bag_pallas(table, idx, combiner=combiner,
                                    interpret=interp)
    return ref.embedding_bag_ref(table, idx, weights, combiner)
