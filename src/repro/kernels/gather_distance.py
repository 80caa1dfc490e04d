"""Fused gather + distance Pallas kernel — the ANNS hot path.

The lazy load phase (Algorithm 1 line 24–27) materializes the miss list
``L``, bulk-loads those vectors, and computes their distances to the
query. On TPU the gather and the distance fuse into one kernel using the
scalar-prefetch idiom (the same indirection pattern as paged attention):
the id matrix sits in SMEM ahead of the grid, the table stays in HBM,
and each grid step DMAs the rows it needs into VMEM and computes their
distances there — the gathered rows never round-trip to HBM.

Layout (what the TPU compiler accepts):

- A grid step covers ``tb`` queries × ``TK = 128`` id slots and writes
  one lane-dense ``(tb, 128)`` output block.
- HBM tables are tiled ``(t, 128)`` with ``t = 32 // itemsize`` rows (8
  for float32, 16 for float16, 32 for int8), and a DMA may not start
  inside a tile. So each id's whole row tile is DMA'd (``t × d`` items,
  one contiguous burst: ``t``× the bytes of the row itself), and the
  row is picked out of it in VMEM. A row tile holds ``32·d`` bytes in
  every dtype (24,576 B at d=768), so int8 and float16 tables move as
  many bytes per id as float32: quantization saves HBM capacity here,
  not DMA bytes. The pick is a dynamic sublane load for float32,
  a shift out of the packed 32-bit word for int8 and float16. Tables
  whose row count is a multiple of :data:`TABLE_ROW_ALIGN` are read in
  place; others are padded by the wrapper (one copy per call).
- DMA and compute do not overlap yet: a query's rows are all fetched
  before its distances are computed.
- The picked rows form a ``(128, d)`` float32 block; the per-row
  reduction yields a ``(128, 1)`` column, turned lane-dense by one
  ``128 × 128`` transpose.
- Padded ids (``-1``) issue no DMA and come back ``+inf``.

The same kernel serves the quantized twin (``dequant_gather_distance``):
per-row scales are gathered by XLA in the wrapper (4 bytes per id) and
multiplied in VMEM, and 'cos' divides by the gathered row's norm
in-kernel, so no float32 or normalized copy of the table is ever made.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TK = 128  # id slots per grid step: one lane-dense output block row
# row count that every payload dtype's HBM tile height divides (int8: 32)
TABLE_ROW_ALIGN = 32
PAYLOAD_DTYPES = (jnp.float32, jnp.float16, jnp.int8)


def _tile_rows(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize


def _f16_bits_to_f32(h):
    """IEEE half bits (low 16 bits of an int32) → exact float32."""
    sign, e, m = (h >> 15) & 1, (h >> 10) & 0x1F, h & 0x3FF
    top = (sign << 31) | (m << 13)
    bits = top | (jnp.where(e == 0x1F, 0xFF, e + 112) << 23)
    sub = m.astype(jnp.float32) * 2.0 ** -24  # zero and subnormals
    return jnp.where(e == 0, jnp.where(sign == 1, -sub, sub),
                     jax.lax.bitcast_convert_type(bits, jnp.float32))


def _pick_row(buf, j, sub):
    """Row ``sub`` of the row tile in ``buf[j]`` as (1, d) f32. Packed
    payloads are read as 32-bit words: rows ``4w..4w+3`` (int8) or
    ``2w, 2w+1`` (float16) share word ``w``, lowest row in the low
    bits."""
    if buf.dtype == jnp.float32:
        return buf[j, pl.ds(sub, 1), :]
    per_word = 4 // buf.dtype.itemsize
    w = buf.bitcast(jnp.int32)[j, pl.ds(sub // per_word, 1), :]
    if buf.dtype == jnp.int8:
        return ((w << (24 - 8 * (sub % 4))) >> 24).astype(jnp.float32)
    return _f16_bits_to_f32((w >> (16 * (sub % 2))) & 0xFFFF)  # int16


def _gather_kernel(ids_ref, q_ref, *rest, metric: str, tb: int,
                   scaled: bool):
    if scaled:
        s_ref, table_hbm, o_ref, buf, rows, sem = rest
    else:
        table_hbm, o_ref, buf, rows, sem = rest
    t = buf.shape[1]
    i = pl.program_id(0)
    j0 = pl.program_id(1) * TK

    def copy(j, row):
        start = pl.multiple_of((row // t) * t, t)
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(start, t)], buf.at[j], sem
        )

    for r in range(tb):  # static: one query of the block at a time
        b = i * tb + r

        def start(j, c):
            row = ids_ref[b, j0 + j]

            @pl.when(row >= 0)
            def _():
                copy(j, row).start()

            return c

        def wait(j, c):
            @pl.when(ids_ref[b, j0 + j] >= 0)
            def _():
                copy(j, 0).wait()

            return c

        def pick(j, c):
            sub = jnp.maximum(ids_ref[b, j0 + j], 0) % t
            rows[pl.ds(j, 1), :] = _pick_row(buf, j, sub)
            return c

        jax.lax.fori_loop(0, TK, start, 0)
        jax.lax.fori_loop(0, TK, wait, 0)
        jax.lax.fori_loop(0, TK, pick, 0)
        x = rows[...]  # (TK, d) f32
        if scaled:  # (1, TK) scales → (TK, 1) column
            x = x * jnp.broadcast_to(s_ref[r:r + 1, :], (TK, TK)).T[:, 0:1]
        q = q_ref[r:r + 1, :].astype(jnp.float32)  # (1, d)
        if metric == "l2":
            diff = x - q
            col = jnp.sum(diff * diff, axis=1, keepdims=True)
        elif metric == "cos":  # q pre-normalized by the wrapper
            col = -jnp.sum(x * q, axis=1, keepdims=True) / (
                jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True)) + 1e-30
            )
        else:  # 'ip'
            col = -jnp.sum(x * q, axis=1, keepdims=True)
        o_ref[r:r + 1, :] = jnp.broadcast_to(col, (TK, TK)).T[0:1, :]


def gather_rows_distance(
    table: jnp.ndarray,  # (N, d) f32 / f16 / int8 payload in HBM
    scales: Optional[jnp.ndarray],  # (N,) per-row scales, or None
    ids: jnp.ndarray,  # (B, K) int32, -1 padded
    Q: jnp.ndarray,  # (B, d)
    metric: str,
    interpret: bool,
) -> jnp.ndarray:
    """(B, K) distances of ``table[ids[b]] * scales[ids[b]]`` to
    ``Q[b]``; +inf for padded ids. Shared by the f32 and dequant
    wrappers (call it under their ``jax.jit``)."""
    N, d = table.shape
    B, K = ids.shape
    if table.dtype not in PAYLOAD_DTYPES:
        raise ValueError(f"table dtype {table.dtype} is not one of "
                         f"{[jnp.dtype(t).name for t in PAYLOAD_DTYPES]}")
    ids = ids.astype(jnp.int32)
    if metric == "cos":
        Q = Q / (jnp.linalg.norm(Q, axis=-1, keepdims=True) + 1e-30)
    tb = B if B < 8 else 8
    pb, pk = (-B) % tb, (-K) % TK
    ids_p = jnp.pad(ids, ((0, pb), (0, pk)), constant_values=-1)
    Bp, Kp = ids_p.shape
    if table.dtype == jnp.float16:  # Mosaic takes no f16 operand: its bits
        table = jax.lax.bitcast_convert_type(table, jnp.int16)
    t = _tile_rows(table.dtype)
    if N % t:
        table = jnp.pad(table, ((0, (-N) % t), (0, 0)))
    operands = [ids_p, jnp.pad(Q.astype(jnp.float32), ((0, pb), (0, 0)))]
    in_specs = [pl.BlockSpec((tb, d), lambda i, j, ids_ref: (i, 0))]
    if scales is not None:
        s = scales.astype(jnp.float32)[jnp.clip(ids_p, 0, N - 1)]
        operands.append(s)
        in_specs.append(pl.BlockSpec((tb, TK), lambda i, j, ids_ref: (i, j)))
    operands.append(table)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, metric=metric, tb=tb,
                          scaled=scales is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bp // tb, Kp // TK),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tb, TK), lambda i, j, ids_ref: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((TK, t, d), table.dtype),  # row tiles
                pltpu.VMEM((TK, d), jnp.float32),  # picked rows
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
        name="gather_distance",
        interpret=interpret,
    )(*operands)
    return jnp.where(ids >= 0, out[:B, :K], jnp.inf)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_distance_pallas(
    table: jnp.ndarray,  # (N, d) — stays in HBM; rows DMA'd on demand
    ids: jnp.ndarray,  # (B,) int32, -1 padded
    q: jnp.ndarray,  # (d,)
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Distances (B,) of table[ids] to q; +inf for padded ids."""
    return gather_rows_distance(
        table, None, ids[None, :], q[None, :], metric, interpret
    )[0]


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_distance_batch_pallas(
    table: jnp.ndarray,  # (N, d) — stays in HBM; rows DMA'd on demand
    ids: jnp.ndarray,  # (B, K) int32, -1 padded — per-query miss lists
    Q: jnp.ndarray,  # (B, d) — one query per id row
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched fused gather + distance: (B, K) ids × (B, d) queries →
    (B, K) distances, +inf for padded ids.

    The TPU-native compute path for the batched load phase's distance
    work (DESIGN.md §5), dispatched via ``ops.gather_distance_batch``
    (the host-driven engine computes load-phase distances from the
    already-fetched vectors instead); nothing is materialized at
    (B, K, d).
    """
    return gather_rows_distance(table, None, ids, Q, metric, interpret)
