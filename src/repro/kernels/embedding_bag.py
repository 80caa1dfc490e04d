"""Embedding-bag Pallas kernel (recsys substrate).

JAX has no native EmbeddingBag; the framework-level implementation is
``jnp.take`` + ``segment_sum`` (:mod:`repro.models.embeddings`). This
kernel is the TPU hot-path variant for the *padded multi-hot* layout used
by the recsys archs: ``idx (B, S)`` with -1 padding → ``out (B, d)``.

Pattern: grid ``(B_tiles, S)``; dimension 1 walks the bag slots. Each step
DMAs one table row-block per bag row via scalar-prefetch indexing and
accumulates into the output block (revisited across the S dimension) —
gather and reduce fused, rows never hit HBM twice.

The grid here is (B, S) with (1, d) row blocks for clarity; production
block sizes would group bag rows to amortize DMA setup (same structure).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, row_ref, o_ref, *, n_slots: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    b = pl.program_id(0)
    valid = idx_ref[b * n_slots + s] >= 0
    x = row_ref[...].astype(jnp.float32)  # (1, d)
    o_ref[...] += jnp.where(valid, x, 0.0)


@functools.partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag_pallas(
    table: jnp.ndarray,  # (V, d)
    idx: jnp.ndarray,  # (B, S) int32, -1 padded
    combiner: str = "sum",
    interpret: bool = False,
) -> jnp.ndarray:
    V, d = table.shape
    B, S = idx.shape
    flat = idx.reshape(-1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S),
        in_specs=[
            # raw (possibly -1) ids are prefetched; the index_map clips so
            # the DMA is always in-bounds, while the kernel body sees the
            # raw id and zeroes the padded contribution.
            pl.BlockSpec(
                (1, d),
                lambda b, s, idx_ref: (jnp.maximum(idx_ref[b * S + s], 0), 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, d), lambda b, s, idx_ref: (b, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_bag_kernel, n_slots=S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, d), jnp.float32),
        interpret=interpret,
    )(flat, table)
    if combiner == "mean":
        cnt = jnp.maximum(jnp.sum((idx >= 0).astype(jnp.float32), 1), 1e-9)
        out = out / cnt[:, None]
    return out
