"""Blocked distance-matrix Pallas kernel (the paper's Wasm compute tier).

The paper moves distance calculation — >40% of query compute (Fig. 1b) —
onto the compiled tier. On TPU that tier is the MXU: the L2 distance is
rewritten in matmul form

    ||q - x||^2 = ||q||^2 - 2 q·x + ||x||^2

so the (B, N) distance matrix is one (B, d) × (d, N) matmul (MXU) plus two
rank-1 norm corrections (VPU). Tiling: (TQ=128, d) × (d, TN=128) blocks in
VMEM; d is blocked too for very wide embeddings so the working set stays
VMEM-sized; partial products accumulate in an f32 VMEM scratch across the
d-grid dimension.

VMEM budget at defaults (TQ=TN=128, TD=512):
  q block 128×512×4 = 256 KiB, x block 256 KiB, out 64 KiB, acc 64 KiB
  → ~0.6 MiB of ~16 MiB/core. MXU dims all multiples of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEF_TQ = 128
DEF_TN = 128
DEF_TD = 512


def _dist_kernel(q_ref, x_ref, o_ref, acc_ref, *, metric: str, n_d: int):
    """Grid = (nq_tiles, nn_tiles, nd_tiles); d innermost (accumulation)."""
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)  # (TQ, TD)
    x = x_ref[...].astype(jnp.float32)  # (TN, TD)
    g = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TQ, TN) MXU
    if metric == "l2":
        qn = jnp.sum(q * q, axis=-1, keepdims=True)  # (TQ, 1)
        xn = jnp.sum(x * x, axis=-1, keepdims=True).T  # (1, TN)
        acc_ref[...] += qn + xn - 2.0 * g
    elif metric == "ip":
        acc_ref[...] += -g
    else:  # cos: accumulate dot and norms, normalize at the end
        acc_ref[...] += -g  # caller pre-normalizes rows for cos

    @pl.when(kd == n_d - 1)
    def _done():
        out = acc_ref[...]
        if metric == "l2":
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("metric", "tq", "tn", "td", "interpret"),
)
def distance_matrix_pallas(
    Q: jnp.ndarray,  # (B, d)
    X: jnp.ndarray,  # (N, d)
    metric: str = "l2",
    tq: int = DEF_TQ,
    tn: int = DEF_TN,
    td: int = DEF_TD,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, N) f32 distances. Pads all dims to tile multiples.

    'cos' is computed by row-normalizing inputs (host of the kernel) and
    reusing the 'ip' accumulation — one pass, no extra kernel state.
    """
    B, d = Q.shape
    N, _ = X.shape
    if metric == "cos":
        Q = Q / (jnp.linalg.norm(Q, axis=-1, keepdims=True) + 1e-30)
        X = X / (jnp.linalg.norm(X, axis=-1, keepdims=True) + 1e-30)
        metric = "ip"
    pb = (-B) % tq
    pn = (-N) % tn
    pd = (-d) % td
    Qp = jnp.pad(Q, ((0, pb), (0, pd)))
    Xp = jnp.pad(X, ((0, pn), (0, pd)))
    n_q, n_n, n_d = Qp.shape[0] // tq, Xp.shape[0] // tn, Qp.shape[1] // td
    out = pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric, n_d=n_d),
        out_shape=jax.ShapeDtypeStruct((Qp.shape[0], Xp.shape[0]), jnp.float32),
        grid=(n_q, n_n, n_d),
        in_specs=[
            pl.BlockSpec((tq, td), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((tn, td), lambda i, j, kd: (j, kd)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j, kd: (i, j)),
        scratch_shapes=[pltpu_scratch((tq, tn))],
        interpret=interpret,
    )(Qp, Xp)
    return out[:B, :N]


def pltpu_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)
