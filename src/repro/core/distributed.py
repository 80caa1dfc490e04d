"""Distributed WebANNS: mesh-sharded ANNS search (the multi-pod path).

Scaling the paper's engine past one device follows the standard
shard-parallel ANNS design (FAISS/SPANN lineage), expressed TPU-natively
with ``jax.shard_map`` + ``jax.lax`` collectives:

- The vector payload is sharded across the mesh ``data`` (and ``pod``)
  axes. Each shard owns a *local HNSW sub-index* built over its rows —
  each device runs the paper's engine locally (with its own three-tier
  store on real hardware: HBM cache over host-DRAM tier-3).
- A query batch arrives sharded over ``data``; queries are all-gathered
  so every shard scores every query against its sub-index, then per-shard
  top-k candidates are all-gathered and reduced to the global top-k.
  Exactly two collectives per batch — the lazy-batching economics of the
  paper (few, dense transfers beat many small ones) applied at mesh scale.
- ``distributed_brute_force`` is the flat-scan variant (used for recsys
  ``retrieval_cand`` and as the exactness oracle); its local scan is the
  Pallas distance+top-k kernel when available.

The fully-jitted in-shard searcher is the fixed-shape beam search of
:mod:`repro.core.search` vmapped over queries; a ``lax.while_loop`` with
static bounds — this is what the multi-pod dry-run lowers and compiles.

On real TPU the tier-3 of each shard would live in ``pinned_host`` memory
(``NamedSharding(..., memory_kind="pinned_host")``); the CPU backend used
for the dry-run cannot compile host-memory placement (verified), so the
dry-run models tier 3 as shard-resident HBM. This changes no collective
or sharding structure — only the HBM byte count, which the roofline
reports note.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import search as S
from repro.core.graph import HNSWGraph
from repro.core.hnsw import build_hnsw


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "vectors", "neighbors", "levels", "entry", "max_level",
        "row_valid", "base_ids",
    ],
    meta_fields=["metric"],
)
@dataclasses.dataclass
class ShardedIndex:
    """Per-shard HNSW sub-indices in stacked, statically-shaped arrays.

    All shards are padded to identical (rows, layers, degree) so the whole
    structure is one pytree of arrays with a leading shard axis, shardable
    with ``P("data")`` (or ``P(("pod", "data"))``).
    """

    vectors: jnp.ndarray  # (S, rows, d) f32 — padded with +inf rows
    neighbors: jnp.ndarray  # (S, L, rows, deg) i32
    levels: jnp.ndarray  # (S, rows) i32
    entry: jnp.ndarray  # (S,) i32
    max_level: jnp.ndarray  # (S,) i32
    row_valid: jnp.ndarray  # (S, rows) bool
    base_ids: jnp.ndarray  # (S,) i32 — global id of shard row 0
    metric: str = "l2"

    @property
    def n_shards(self) -> int:
        return int(self.vectors.shape[0])

    @property
    def rows(self) -> int:
        return int(self.vectors.shape[1])


def build_sharded_index(
    X: np.ndarray,
    n_shards: int,
    M: int = 16,
    ef_construction: int = 100,
    metric: str = "l2",
    seed: int = 0,
) -> ShardedIndex:
    """Row-shard X and build one HNSW sub-index per shard (offline)."""
    X = np.asarray(X, np.float32)
    n, d = X.shape
    rows = (n + n_shards - 1) // n_shards
    graphs: List[HNSWGraph] = []
    shards: List[np.ndarray] = []
    for s in range(n_shards):
        lo, hi = s * rows, min(n, (s + 1) * rows)
        Xs = X[lo:hi]
        if Xs.shape[0] == 0:
            Xs = X[:1]  # degenerate tail shard: single row, masked out
        graphs.append(
            build_hnsw(Xs, M=M, ef_construction=ef_construction,
                       metric=metric, seed=seed + s)
        )
        shards.append(Xs)
    L = max(g.n_layers for g in graphs)
    deg = max(g.max_degree for g in graphs)
    vec = np.full((n_shards, rows, d), np.float32(3.4e38), np.float32)
    nbr = np.full((n_shards, L, rows, deg), -1, np.int32)
    lev = np.zeros((n_shards, rows), np.int32)
    ent = np.zeros((n_shards,), np.int32)
    mxl = np.zeros((n_shards,), np.int32)
    valid = np.zeros((n_shards, rows), bool)
    base = np.zeros((n_shards,), np.int32)
    for s, (g, Xs) in enumerate(zip(graphs, shards)):
        r = Xs.shape[0]
        vec[s, :r] = Xs
        nbr[s, : g.n_layers, :r, : g.max_degree] = g.neighbors
        lev[s, :r] = g.levels
        ent[s] = g.entry_point
        mxl[s] = g.max_level
        lo = s * rows
        valid[s, : min(r, max(0, n - lo))] = True
        base[s] = min(lo, n - 1)
    return ShardedIndex(
        vectors=jnp.asarray(vec),
        neighbors=jnp.asarray(nbr),
        levels=jnp.asarray(lev),
        entry=jnp.asarray(ent),
        max_level=jnp.asarray(mxl),
        row_valid=jnp.asarray(valid),
        base_ids=jnp.asarray(base),
        metric=metric,
    )


def index_shardings(
    mesh: Mesh, data_axes: Tuple[str, ...] = ("data",)
) -> ShardedIndex:
    """PartitionSpec pytree matching ShardedIndex (shard axis → data axes)."""
    sp = P(data_axes)
    return ShardedIndex(  # type: ignore[arg-type]
        vectors=sp, neighbors=sp, levels=sp, entry=sp, max_level=sp,
        row_valid=sp, base_ids=sp, metric="l2",
    )


# -------------------------------------------------------------- local path


def _local_knn(
    Q: jnp.ndarray,  # (B, d) — full query batch (replicated per shard)
    vectors: jnp.ndarray,  # (rows, d)
    neighbors: jnp.ndarray,  # (L, rows, deg)
    levels: jnp.ndarray,
    entry: jnp.ndarray,
    max_level: jnp.ndarray,
    k: int,
    ef: int,
    metric: str,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vmapped in-shard HNSW search. Returns (dists (B,k), local_ids (B,k))."""

    def one(q):
        ep = jax.lax.cond(
            max_level > 0,
            lambda: S.greedy_descend_inmem(
                q, vectors, neighbors[1:], levels, entry, max_level, metric
            ),
            lambda: entry,
        )
        st = S.search_layer_inmem(
            q, vectors, neighbors[0],
            jnp.full((1,), ep, jnp.int32), ef, metric,
        )
        return st.beam.dists[:k], st.beam.ids[:k]

    return jax.vmap(one)(Q)


def _local_scan(
    Q: jnp.ndarray, vectors: jnp.ndarray, k: int, metric: str,
    row_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Brute-force local shard scan (Pallas kernel hook point)."""
    from repro.kernels import ops as kops

    D = kops.distance_topk_ready(Q, vectors, metric)
    D = jnp.where(row_valid[None, :], D, jnp.inf)
    negd, ids = jax.lax.top_k(-D, k)
    return -negd, ids.astype(jnp.int32)


# ---------------------------------------------------------- mesh programs


def make_distributed_search(
    mesh: Mesh,
    metric: str = "l2",
    k: int = 10,
    ef: int = 64,
    data_axes: Tuple[str, ...] = ("data",),
    mode: str = "hnsw",  # 'hnsw' | 'flat'
    jit: bool = True,
):
    """Build the jitted mesh-wide search program.

    Program per shard: all-gather queries → local search → all-gather
    per-shard (dist, global_id) candidates → global top-k reduce.
    Queries in sharded over ``data``; output replicated over ``model``.
    """
    qspec = P(data_axes, None)

    def local_program(Q_local, vectors, neighbors, levels, entry, max_level,
                      row_valid, base_ids):
        # shard_map gives per-shard blocks with the leading axis stripped
        vectors, neighbors = vectors[0], neighbors[0]
        levels, entry = levels[0], entry[0]
        max_level, row_valid = max_level[0], row_valid[0]
        base = base_ids[0]
        # 1 collective: replicate the query batch across shards
        Q = jax.lax.all_gather(Q_local, data_axes, axis=0, tiled=True)
        if mode == "flat":
            d_loc, i_loc = _local_scan(Q, vectors, k, metric, row_valid)
        else:
            d_loc, i_loc = _local_knn(
                Q, vectors, neighbors, levels, entry, max_level, k, ef,
                metric,
            )
            invalid = ~row_valid[jnp.clip(i_loc, 0, row_valid.shape[0] - 1)]
            d_loc = jnp.where((i_loc < 0) | invalid, jnp.inf, d_loc)
        g_ids = jnp.where(i_loc >= 0, i_loc + base, -1)
        # 2nd collective: gather all shards' candidates
        d_all = jax.lax.all_gather(d_loc, data_axes, axis=1, tiled=True)
        i_all = jax.lax.all_gather(g_ids, data_axes, axis=1, tiled=True)
        # global top-k reduce (identical on every shard)
        negd, sel = jax.lax.top_k(-d_all, k)
        ids = jnp.take_along_axis(i_all, sel, axis=1)
        # return this shard's slice of the query batch results
        bsz = Q_local.shape[0]
        shard_idx = jax.lax.axis_index(data_axes[0]) if len(data_axes) == 1 \
            else (
                jax.lax.axis_index(data_axes[0])
                * jax.lax.axis_size(data_axes[1])
                + jax.lax.axis_index(data_axes[1])
            )
        start = shard_idx * bsz
        return (
            jax.lax.dynamic_slice_in_dim(-negd, start, bsz, 0),
            jax.lax.dynamic_slice_in_dim(ids, start, bsz, 0),
        )

    ispec = P(data_axes)
    sharded = jax.shard_map(
        local_program,
        mesh=mesh,
        in_specs=(qspec, ispec, ispec, ispec, ispec, ispec, ispec, ispec),
        out_specs=(qspec, qspec),
        check_vma=False,
    )

    def search_fn(Q, index: ShardedIndex):
        return sharded(
            Q, index.vectors, index.neighbors, index.levels, index.entry,
            index.max_level, index.row_valid, index.base_ids,
        )

    if not jit:
        return search_fn
    return jax.jit(search_fn)


def distributed_brute_force(mesh: Mesh, metric: str = "l2", k: int = 10,
                            data_axes: Tuple[str, ...] = ("data",)):
    """Flat-scan variant (exact; retrieval_cand path)."""
    return make_distributed_search(
        mesh, metric=metric, k=k, data_axes=data_axes, mode="flat"
    )


# ----------------------------------------------- engine-facing sharded path
#
# The substrate above searches per-shard LOCAL sub-indices — recall depends
# on every shard's sub-graph, so its results are NOT comparable to the
# single-device engine. The path below is different (DESIGN.md §10): ONE
# global HNSW graph whose vector table, tier-2/3 payload, and adjacency
# rows are row-sharded over a 1-D ("shard",) mesh. Every shard executes
# the SAME replicated beam-search control flow (beam, explored flags, hop
# loop) while touching only its own rows:
#
# - the hop's adjacency row is contributed by the owner shard and
#   broadcast with ``pmax`` (PAD = -1 loses to any real id);
# - visited bits live per-shard, over local rows only ((B, rows) not
#   (B, N)) — the one piece of state that shards the O(N) memory;
# - each shard computes distances for its fresh local neighbors via the
#   gather-distance / dequant-gather-distance kernels and emits a
#   (global_id, dist) candidate list; candidates are all-gathered and
#   merged into the beam by the fused cross-shard top-k
#   (``kernels.ops.merge_topk``).
#
# Bit-parity with the single-device batched driver (enforced by
# tests/test_sharded_parity.py) rests on three invariants:
#
# 1. owner distances are bit-identical to ``cache_lookup`` +
#    ``point_distance`` (same gather/dequant/reduce formulas);
# 2. the all-gathered candidates are flattened SLOT-MAJOR (position
#    p = slot·S + shard), and each slot has at most one non-sentinel
#    entry (global ids have exactly one owner), so merge_topk's
#    position tie-break reproduces ``beam_merge``'s concat order;
# 3. the while-loop control state (beam, hops) is replicated — every
#    shard takes the same trip count, like vmap-of-while_loop masking.


@dataclasses.dataclass
class ShardedEngineState:
    """Mesh-sharded device state of ONE global index (DESIGN.md §10).

    All array leaves carry a leading shard axis placed on the mesh's
    ``"shard"`` axis; shard ``s`` owns global ids ``[s·rows, (s+1)·rows)``
    with rows padded past ``n`` marked tombstoned.
    """

    table: jnp.ndarray  # (S, rows_pad, d) payload — f32, or int8/f16
    scales: jnp.ndarray  # (S, rows_pad) f32 int8 scales; (S, 1) dummy
    neighbors: jnp.ndarray  # (S, L, rows, deg) int32 GLOBAL-id adjacency
    tombstones: jnp.ndarray  # (S, rows) bool — padding rows True
    n: int  # global id-space size
    metric: str
    precision: str

    @property
    def n_shards(self) -> int:
        return int(self.table.shape[0])


def build_sharded_engine_state(
    backend,
    neighbors: np.ndarray,  # (L, N, deg) int32 global adjacency
    tombstones: np.ndarray,  # (N,) bool
    mesh: Mesh,
    precision: str = "float32",
    metric: str = "l2",
) -> ShardedEngineState:
    """Stage the engine's index onto a ("shard",) mesh.

    Rows are fetched per mesh shard (``fetch_range`` when the backend
    provides it — a :class:`~repro.core.storage.ShardedFileBackend` then
    touches only the files overlapping each shard's row range, keeping
    tier-3 reads shard-local) and quantized per shard; the int8/f16
    codec is per-row (``quant.quantize_np``), so per-shard quantization
    is bit-identical to quantizing the whole table at once.

    Each shard's payload rows are padded to a multiple of
    ``TABLE_ROW_ALIGN`` so the gather kernels read the table in place;
    the padding rows are never addressed (ownership follows ``rows``).
    """
    from repro.core import quant
    from repro.core.graph import PAD
    from repro.core.storage import mesh_shard_ranges
    from repro.kernels.gather_distance import TABLE_ROW_ALIGN

    n_shards = mesh.shape["shard"]
    L, n, deg = neighbors.shape
    d = backend.dim
    rows = -(-n // n_shards)
    rows_pad = -(-rows // TABLE_ROW_ALIGN) * TABLE_ROW_ALIGN
    pay_dtype = {"int8": np.int8, "float16": np.float16,
                 "float32": np.float32}[precision]
    table = np.zeros((n_shards, rows_pad, d), pay_dtype)
    scales = np.zeros(
        (n_shards, rows_pad if precision == "int8" else 1), np.float32
    )
    for s, (lo, hi) in enumerate(mesh_shard_ranges(n, n_shards)):
        if hi <= lo:
            continue
        blk = (
            backend.fetch_range(lo, hi) if hasattr(backend, "fetch_range")
            else backend.fetch(np.arange(lo, hi, dtype=np.int64))
        )
        if precision == "float32":
            table[s, : hi - lo] = blk
        else:
            pay, sc = quant.quantize_np(blk, precision)
            table[s, : hi - lo] = pay
            if precision == "int8":
                scales[s, : hi - lo] = sc
    nbr = np.full((L, n_shards * rows, deg), PAD, np.int32)
    nbr[:, :n] = neighbors
    nbr = nbr.reshape(L, n_shards, rows, deg).transpose(1, 0, 2, 3)
    tombs = np.ones((n_shards * rows,), bool)
    tombs[:n] = np.asarray(tombstones, bool)
    tombs = tombs.reshape(n_shards, rows)
    sharding = NamedSharding(mesh, P("shard"))
    return ShardedEngineState(
        table=jax.device_put(table, sharding),
        scales=jax.device_put(scales, sharding),
        neighbors=jax.device_put(np.ascontiguousarray(nbr), sharding),
        tombstones=jax.device_put(tombs, sharding),
        n=n,
        metric=metric,
        precision=precision,
    )


@functools.lru_cache(maxsize=None)
def sharded_layer_program(
    mesh: Mesh,
    ef: int,
    metric: str,
    quantized: bool,
    max_hops: int = 100000,
):
    """Jitted shard_map program for ONE layer of the sharded beam search.

    Call signature: ``prog(Q (B,d), entry (B,E), table (S,rows,d),
    scales (S,rows), neighbors_l (S,rows,deg), tombs (S,rows)) ->
    (beam_ids (B,ef), beam_dists (B,ef), beam_explored (B,ef),
    n_hops (B,), n_dist (B,))`` — the layer's final beam, replicated.

    Semantically this is ``batch_seed_state`` + ``batch_search_phase``
    with a 100%-resident tier-2 (each shard's slab IS its table rows),
    manually batched so the cross-shard collectives run at full batch
    width. Lane masking via ``active`` replicates vmap-of-while_loop
    select semantics, keeping per-query trip behavior identical to the
    single-device batched driver.
    """
    from repro.kernels import ops as kops

    n_shards = int(mesh.shape["shard"])

    def program(Q, entry, table, scales, neighbors_l, tombs):
        # shard_map passes per-shard blocks with a length-1 leading axis
        table, scales = table[0], scales[0]
        neighbors_l, tombs = neighbors_l[0], tombs[0]
        B = Q.shape[0]
        rows, deg = neighbors_l.shape
        lo = jax.lax.axis_index("shard").astype(jnp.int32) * rows
        brow = jnp.arange(B, dtype=jnp.int32)[:, None]
        inf = jnp.float32(jnp.inf)

        def dist_fn(loc_ids):  # (B, K) LOCAL ids (-1 masked) -> (B, K) f32
            if quantized:
                return kops.dequant_gather_distance_batch(
                    table, scales, loc_ids, Q, metric
                )
            return kops.gather_distance_batch(table, loc_ids, Q, metric)

        # ---- seed (seed_state semantics, owner-computed distances)
        g = entry.astype(jnp.int32)  # (B, E) global ids
        owned = (g >= lo) & (g < lo + rows)
        loc = jnp.clip(g - lo, 0, rows - 1)
        visited = jnp.broadcast_to(tombs[None, :], (B, rows))
        vbit = jnp.take_along_axis(visited, loc, axis=1) & owned
        vis_any = jax.lax.psum(vbit.astype(jnp.float32), "shard") > 0
        valid = (g >= 0) & ~vis_any
        present = jax.lax.psum(owned.astype(jnp.float32), "shard") > 0
        usable = valid & present
        d_loc = dist_fn(jnp.where(owned, loc, -1))
        # owner contributes its exact f32 distance, others 0.0 — the
        # psum adds +0.0 to one finite value, which is exact in IEEE
        d_all = jax.lax.psum(jnp.where(owned, d_loc, 0.0), "shard")
        cat_ids = jnp.concatenate(
            [jnp.full((B, ef), -1, jnp.int32), jnp.where(usable, g, -1)], 1
        )
        cat_d = jnp.concatenate(
            [jnp.full((B, ef), inf), jnp.where(usable, d_all, inf)], 1
        )
        cat_d = jnp.where(cat_ids >= 0, cat_d, inf)
        _, order = jax.lax.top_k(-cat_d, ef)  # beam_merge tie semantics
        beam_ids = jnp.take_along_axis(cat_ids, order, 1)
        beam_d = jnp.take_along_axis(cat_d, order, 1)
        beam_e = jnp.zeros((B, ef), bool)
        visited = visited.at[
            brow, jnp.where(valid & owned, g - lo, rows)
        ].set(True, mode="drop")

        # ---- hop loop (search_phase body, cross-shard)
        col_ef = jax.lax.broadcasted_iota(jnp.int32, (B, ef), 1)

        def cond(carry):
            bi, bd, be, vis, hops, nd = carry
            return jnp.any(
                jnp.any((bi >= 0) & ~be, axis=1) & (hops < max_hops)
            )

        def body(carry):
            bi, bd, be, vis, hops, nd = carry
            unexp = (bi >= 0) & ~be
            active = jnp.any(unexp, axis=1) & (hops < max_hops)  # (B,)
            j = jnp.argmin(jnp.where(unexp, bd, inf), axis=1)
            j = j.astype(jnp.int32)
            c = jnp.take_along_axis(bi, j[:, None], 1)[:, 0]  # (B,)
            be = be | ((col_ef == j[:, None]) & active[:, None])
            # owner shard broadcasts c's adjacency row (PAD loses pmax)
            own_c = (c >= lo) & (c < lo + rows)
            nbr_loc = neighbors_l[jnp.clip(c - lo, 0, rows - 1)]
            nbrs = jax.lax.pmax(
                jnp.where(own_c[:, None], nbr_loc, -1), "shard"
            )  # (B, deg) global ids
            own_n = (nbrs >= lo) & (nbrs < lo + rows)
            loc_n = jnp.clip(nbrs - lo, 0, rows - 1)
            fresh = own_n & ~jnp.take_along_axis(vis, loc_n, axis=1)
            vis = vis.at[
                brow, jnp.where(fresh & active[:, None], nbrs - lo, rows)
            ].set(True, mode="drop")
            d_loc = dist_fn(jnp.where(fresh, loc_n, -1))
            n_new = jax.lax.psum(
                jnp.sum(fresh.astype(jnp.int32), axis=1), "shard"
            )
            # per-shard candidates, all-gathered and flattened SLOT-MAJOR
            # (p = slot·S + shard) — ≤1 owner per slot, so merge_topk's
            # position tie-break reproduces beam_merge's concat order
            cand_i = jax.lax.all_gather(
                jnp.where(fresh, nbrs, -1), "shard", axis=0
            )
            cand_d = jax.lax.all_gather(
                jnp.where(fresh, d_loc, inf), "shard", axis=0
            )
            cand_i = jnp.transpose(cand_i, (1, 2, 0)).reshape(
                B, deg * n_shards
            )
            cand_d = jnp.transpose(cand_d, (1, 2, 0)).reshape(
                B, deg * n_shards
            )
            md, mi, msrc = kops.merge_topk(
                jnp.concatenate([bd, cand_d], axis=1),
                jnp.concatenate([bi, cand_i], axis=1),
                ef,
            )
            # survivors carried over from the beam keep their explored
            # flag (src < ef); fresh candidates arrive unexplored
            from_beam = (msrc >= 0) & (msrc < ef)
            me = jnp.take_along_axis(
                be, jnp.clip(msrc, 0, ef - 1), axis=1
            ) & from_beam
            bi = jnp.where(active[:, None], mi, bi)
            bd = jnp.where(active[:, None], md, bd)
            be = jnp.where(active[:, None], me, be)
            return (
                bi, bd, be, vis,
                hops + active.astype(jnp.int32),
                nd + jnp.where(active, n_new, 0),
            )

        init = (
            beam_ids, beam_d, beam_e, visited,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        )
        bi, bd, be, _, hops, nd = jax.lax.while_loop(cond, body, init)
        return bi, bd, be, hops, nd

    rep, shd = P(), P("shard")
    return jax.jit(jax.shard_map(
        program,
        mesh=mesh,
        in_specs=(rep, rep, shd, shd, shd, shd),
        out_specs=(rep, rep, rep, rep, rep),
        check_vma=False,
    ))
