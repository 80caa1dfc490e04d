"""Partial top-k Pallas kernel (the paper's "sorting" compute hot spot).

Fig. 1b attributes ~50% of query compute to sorting/candidate management.
On TPU we implement split-K top-k (FlashDecoding-style): the (B, N)
distance matrix is tiled over columns; each grid step selects the k
smallest within its (TB, TN) tile by iterative masked-min extraction
(k ≤ 64, VPU-friendly — no data-dependent control flow), writing per-tile
candidates to (B, n_tiles·k); a cheap final ``lax.top_k`` merge over the
(n_tiles·k) survivors happens in the jitted wrapper. Total work drops from
O(N log N) sort to O(N·k/TN + T·k log(T·k)).

VMEM: (TB=128, TN=512) f32 tile = 256 KiB + two (128, 128) outputs (k
padded to a lane multiple) = 128 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_TB = 128
DEF_TN = 512
LANES = 128


def _first_index(hit, col, width: int):
    """(TB, 1) lowest column where ``hit`` holds (argmin's tie break)."""
    return jnp.min(jnp.where(hit, col, width), axis=1, keepdims=True)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _topk_tile_kernel(d_ref, od_ref, oi_ref, *, k: int, tn: int):
    """Select k smallest in this (TB, TN) tile via iterative extraction.

    Extraction ``i`` lands in output lane ``i`` by an iota-compare
    select (Mosaic has no dynamic_update_slice); the output block is
    ``kp`` = k rounded up to 128 lanes, sliced back by the wrapper."""
    j = pl.program_id(1)
    d = d_ref[...].astype(jnp.float32)  # (TB, TN)
    tb = d.shape[0]
    kp = od_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, tn), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)
    base = j * tn

    def body(i, carry):
        d_cur, od, oi = carry
        m = jnp.min(d_cur, axis=1, keepdims=True)  # (TB, 1)
        am = _first_index(d_cur == m, col, tn)  # (TB, 1)
        od = jnp.where(lane == i, m, od)
        oi = jnp.where(lane == i, am + base, oi)
        # mask out the extracted element
        d_cur = jnp.where(col == am, jnp.inf, d_cur)
        return d_cur, od, oi

    od0 = jnp.full((tb, kp), jnp.inf, jnp.float32)
    oi0 = jnp.full((tb, kp), -1, jnp.int32)
    _, od, oi = jax.lax.fori_loop(0, k, body, (d, od0, oi0))
    od_ref[...] = od
    oi_ref[...] = oi


@functools.partial(
    jax.jit, static_argnames=("k", "tb", "tn", "interpret")
)
def topk_pallas(
    D: jnp.ndarray,  # (B, N) distances
    k: int,
    tb: int = DEF_TB,
    tn: int = DEF_TN,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise smallest-k: returns (dists (B, k), ids (B, k))."""
    B, N = D.shape
    pb = (-B) % tb
    pn = (-N) % tn
    Dp = jnp.pad(D, ((0, pb), (0, pn)), constant_values=jnp.inf)
    nb, nn = Dp.shape[0] // tb, Dp.shape[1] // tn
    kp = _round_up(k, LANES)
    od, oi = pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=k, tn=tn),
        out_shape=(
            jax.ShapeDtypeStruct((Dp.shape[0], nn * kp), jnp.float32),
            jax.ShapeDtypeStruct((Dp.shape[0], nn * kp), jnp.int32),
        ),
        grid=(nb, nn),
        in_specs=[pl.BlockSpec((tb, tn), lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec((tb, kp), lambda i, j: (i, j)),
            pl.BlockSpec((tb, kp), lambda i, j: (i, j)),
        ),
        name="topk",
        interpret=interpret,
    )(Dp)
    # final merge over nn*k survivors per row (cheap)
    od = od[:B].reshape(B, nn, kp)[:, :, :k].reshape(B, nn * k)
    oi = oi[:B].reshape(B, nn, kp)[:, :, :k].reshape(B, nn * k)
    negd, sel = jax.lax.top_k(-od, k)
    ids = jnp.take_along_axis(oi, sel, axis=1)
    return -negd, ids


MERGE_TB = 8
MERGE_TM = 128


def _merge_topk_kernel(d_ref, i_ref, od_ref, oi_ref, os_ref, *, k: int):
    """Dedup + k-smallest over one (TB, M) candidate tile.

    Same iterative masked-min extraction as ``_topk_tile_kernel``, with two
    twists: sentinel entries (id < 0 or non-finite dist) never win, and
    after each extraction every position carrying the winner's id is
    masked, so duplicates of one node arriving from several shards
    collapse to their best copy. argmin's first-index tie break gives the
    lowest-input-position order the sharded beam merge relies on.
    """
    d = d_ref[...].astype(jnp.float32)  # (TB, M)
    ids = i_ref[...]  # (TB, M)
    tb, m = d.shape
    kp = od_ref.shape[1]
    # |d| < inf is false for ±inf and nan alike
    d = jnp.where((ids >= 0) & (jnp.abs(d) < jnp.inf), d, jnp.inf)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, m), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)

    def body(i, carry):
        d_cur, od, oi, osrc = carry
        mn = jnp.min(d_cur, axis=1, keepdims=True)  # (TB, 1)
        am = _first_index(d_cur == mn, col, m)  # (TB, 1)
        sel = col == am
        # exactly one column matches → sum pulls out ids[am] (VPU-friendly
        # one-hot gather; per-row dynamic indexing is TPU-hostile)
        v = jnp.sum(jnp.where(sel, ids, 0), axis=1, keepdims=True)
        ok = mn < jnp.inf
        at = lane == i
        od = jnp.where(at, jnp.where(ok, mn, jnp.inf), od)
        oi = jnp.where(at, jnp.where(ok, v, -1), oi)
        osrc = jnp.where(at, jnp.where(ok, am, -1), osrc)
        # retire the winner and every duplicate of its id
        hit = sel | (ok & (ids == v))
        return jnp.where(hit, jnp.inf, d_cur), od, oi, osrc

    od0 = jnp.full((tb, kp), jnp.inf, jnp.float32)
    oi0 = jnp.full((tb, kp), -1, jnp.int32)
    _, od, oi, osrc = jax.lax.fori_loop(0, k, body, (d, od0, oi0, oi0))
    od_ref[...] = od
    oi_ref[...] = oi
    os_ref[...] = osrc


@functools.partial(jax.jit, static_argnames=("k", "tb", "interpret"))
def merge_topk_pallas(
    dists: jnp.ndarray,  # (B, M) candidate distances
    ids: jnp.ndarray,  # (B, M) int32 global ids, -1 sentinel padded
    k: int,
    tb: int = MERGE_TB,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused cross-shard top-k merge; semantics of ``ref.merge_topk_ref``.

    Returns (dists (B, k), ids (B, k), src (B, k)) with src = winner's
    input position (−1 on padding rows). M is padded to a lane multiple;
    the whole candidate row fits one block (M = ef + n_shards·degree is a
    few hundred), so the grid only tiles the batch.
    """
    B, M = dists.shape
    pb = (-B) % tb
    pm = (-max(M, k)) % MERGE_TM + max(0, k - M)
    Dp = jnp.pad(
        dists.astype(jnp.float32), ((0, pb), (0, pm)),
        constant_values=jnp.inf,
    )
    Ip = jnp.pad(
        ids.astype(jnp.int32), ((0, pb), (0, pm)), constant_values=-1
    )
    mp = Dp.shape[1]
    kp = _round_up(k, LANES)
    out_block = pl.BlockSpec((tb, kp), lambda i: (i, 0))
    od, oi, osrc = pl.pallas_call(
        functools.partial(_merge_topk_kernel, k=k),
        out_shape=(
            jax.ShapeDtypeStruct((Dp.shape[0], kp), jnp.float32),
            jax.ShapeDtypeStruct((Dp.shape[0], kp), jnp.int32),
            jax.ShapeDtypeStruct((Dp.shape[0], kp), jnp.int32),
        ),
        grid=(Dp.shape[0] // tb,),
        in_specs=[
            pl.BlockSpec((tb, mp), lambda i: (i, 0)),
            pl.BlockSpec((tb, mp), lambda i: (i, 0)),
        ],
        out_specs=(out_block, out_block, out_block),
        name="merge_topk",
        interpret=interpret,
    )(Dp, Ip)
    return od[:B, :k], oi[:B, :k], osrc[:B, :k]
