"""Code-gather + LUT-accumulate (ADC) Pallas kernels (DESIGN.md §12).

The product-quantized twin of ``dequant_gather_distance.py``: the table
rows live in HBM as (N, M) uint8 PQ codes — M bytes per vector — and the
caller has already built the per-query lookup table ``lut`` (q against
ALL centroids, ``repro.core.pq.build_lut_*``). The kernel selects each
code's table entries and accumulates them into the asymmetric distance
— no decoded vector, in any dtype, is ever materialized. The codes
occupy ``4·d / M``× fewer HBM bytes than float32 rows (32× at d=64,
M=8), which is what lets the DRAM-free ``precision="pq"`` mode keep its
table on the device.

A code row is M bytes, far below one DMA's worth, so the wrapper
gathers the ``(B, K_ids, M)`` codes with XLA, widens them to int32 (4×
the bytes of the codes) and lays them out slot-major on lanes; it also
transposes the LUTs to ``(B, L, C, M)`` on every call. A grid step
scores ``tb`` queries × 128 id slots. For each subspace m the code row
``(1, 128)`` is compared against a centroid iota ``(C, 128)``, the
one-hot selects the LUT column ``lut[l, m, :]`` (held as ``(C, 1)``),
and a sublane sum pulls the entry out — the output is lane-dense with
no transpose in the kernel.

Bit-match contract (asserted in tests): the LUT entry select is an
exact gather (one-hot select–sum — additions of 0.0 are exact) and
the subspace accumulation is an unrolled left-to-right float32 chain,
the same sequence ``pq.adc_distance_np`` and the jnp ref run, so all
three agree bit-for-bit in single and batched forms.

Metrics: 'l2' and 'ip' accumulate a single table (L=1). 'cos' rides a
second squared-norm table (L=2) and finishes with
``-s1 / (sqrt(s2) + 1e-30)`` — the query was normalized at LUT build.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TK = 128  # id slots per grid step (lanes of the output block)


def _adc_kernel(code_ref, lut_ref, o_ref, *, metric: str, tb: int):
    """code_ref (tb, M, TK) int32; lut_ref (tb, L, C, M) f32."""
    _, M, tk = code_ref.shape
    _, L, C, _ = lut_ref.shape
    cent = jax.lax.broadcasted_iota(jnp.int32, (C, tk), 0)

    # one query per iteration: unrolling the queries too would give each
    # of the tb·M·L (C, TK) selects its own VMEM buffer (24 MiB at tb=8,
    # M=16, L=2 — over v5e's 16 MiB scoped limit)
    def query(r, c):
        acc = [jnp.zeros((1, tk), jnp.float32) for _ in range(L)]
        for m in range(M):  # unrolled left-to-right chain (bit-match order)
            onehot = cent == code_ref[r, m:m + 1, :]  # (C, TK)
            for lv in range(L):
                col = lut_ref[r, lv, :, m:m + 1]  # (C, 1)
                acc[lv] = acc[lv] + jnp.sum(
                    jnp.where(onehot, col, 0.0), axis=0, keepdims=True
                )
        if metric == "cos":
            o_ref[pl.ds(r, 1), :] = -acc[0] / (jnp.sqrt(acc[1]) + 1e-30)
        else:
            o_ref[pl.ds(r, 1), :] = acc[0]
        return c

    jax.lax.fori_loop(0, tb, query, 0)


def _adc_batch(codes, luts, ids, metric: str, interpret: bool):
    N, M = codes.shape
    B, L, _, C = luts.shape
    _, K = ids.shape
    tb = B if B < 8 else 8
    pb, pk = (-B) % tb, (-K) % TK
    ids_p = jnp.pad(ids.astype(jnp.int32), ((0, pb), (0, pk)),
                    constant_values=-1)
    Bp, Kp = ids_p.shape
    # (Bp, M, Kp): slots on lanes
    c = jnp.swapaxes(codes[jnp.clip(ids_p, 0, N - 1)].astype(jnp.int32),
                     1, 2)
    lt = jnp.pad(jnp.swapaxes(luts.astype(jnp.float32), 2, 3),
                 ((0, pb), (0, 0), (0, 0), (0, 0)))  # (Bp, L, C, M)
    out = pl.pallas_call(
        functools.partial(_adc_kernel, metric=metric, tb=tb),
        grid=(Bp // tb, Kp // TK),
        in_specs=[
            pl.BlockSpec((tb, M, TK), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tb, L, C, M), lambda i, j: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, TK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
        name="adc_gather_distance",
        interpret=interpret,
    )(c, lt)
    return jnp.where(ids >= 0, out[:B, :K], jnp.inf)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def adc_gather_distance_pallas(
    codes: jnp.ndarray,  # (N, M) uint8 PQ codes in HBM
    lut: jnp.ndarray,  # (L, M, K) f32 per-query table (build_lut_*)
    ids: jnp.ndarray,  # (B,) int32, -1 padded
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """ADC distances (B,) of codes[ids] to the LUT's query; +inf pad."""
    return _adc_batch(codes, lut[None], ids[None], metric, interpret)[0]


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def adc_gather_distance_batch_pallas(
    codes: jnp.ndarray,  # (N, M) uint8 PQ codes
    luts: jnp.ndarray,  # (B, L, M, K) f32 — one table per query
    ids: jnp.ndarray,  # (B, K_ids) int32, -1 padded — per-query lists
    metric: str = "l2",
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched ADC: (B, K_ids) ids × (B, L, M, K) tables → (B, K_ids)
    f32 distances, +inf for padded ids."""
    return _adc_batch(codes, luts, ids, metric, interpret)
