"""Fused dequant + gather + distance Pallas kernels (DESIGN.md §7).

The quantized twin of ``gather_distance.py``: the table rows live in HBM
as int8 (or float16) with one float32 scale per row. The row gather is
the same kernel (``gather_distance.gather_rows_distance``): each id's
quantized row tile is DMA'd into VMEM, the row is dequantized there,
and only distances leave the kernel — no float32 copy of the table (or
even of the gathered rows) is ever materialized in HBM. The per-row
scales are gathered by XLA in the wrapper (4 bytes per id). A row tile
is ``32·d`` bytes whatever the dtype, so this kernel DMAs as many bytes
per id as the float32 one; what quantization saves is HBM capacity.

Metrics: 'l2' and 'ip' as usual. 'cos' normalizes the query in the
wrapper and divides by the gathered row's norm in-kernel (normalizing
the table up front would materialize the float32 copy the kernel
exists to avoid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gather_distance import gather_rows_distance


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def dequant_gather_distance_pallas(
    table: jnp.ndarray,  # (N, d) int8/f16/f32 — quantized payload in HBM
    scales: jnp.ndarray,  # (N,) float32 — per-row dequant scales
    ids: jnp.ndarray,  # (B,) int32, -1 padded
    q: jnp.ndarray,  # (d,) float32
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Distances (B,) of dequantized table[ids] to q; +inf for padding."""
    return gather_rows_distance(
        table, scales, ids[None, :], q[None, :], metric, interpret
    )[0]


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def dequant_gather_distance_batch_pallas(
    table: jnp.ndarray,  # (N, d) int8/f16/f32 quantized payload
    scales: jnp.ndarray,  # (N,) float32 per-row scales
    ids: jnp.ndarray,  # (B, K) int32, -1 padded — per-query miss lists
    Q: jnp.ndarray,  # (B, d) — one query per id row
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched fused dequant + gather + distance: (B, K) ids × (B, d)
    queries → (B, K) float32 distances, +inf for padded ids. Nothing
    is materialized at (B, K, d), in any dtype."""
    return gather_rows_distance(table, scales, ids, Q, metric, interpret)
