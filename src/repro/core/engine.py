"""WebANNS engine: public API + the host-driven phased-lazy query driver.

This mirrors the paper's execution split exactly (§3.2, Fig. 5): the
compute-heavy search phases are compiled (jitted — our "Wasm"), while
fetches from tier 3 are host-side calls orchestrated by the Python driver
(our "JavaScript bridge"). One driver iteration = one ❶–❻ round trip of
the paper's execution-model coordination, except the signal/event-loop
dance is unnecessary on the host — the JAX dispatch boundary plays that
role.

The session API (DESIGN.md §6) is Index/Storage/Session layered:
``WebANNSEngine.open(path)`` reopens a saved :class:`repro.core.index.
Index` (initialization-stage bulk load, one access per shard) over any
:class:`repro.core.storage.StorageBackend`; ``engine.save(path)``
persists the artifact; :meth:`WebANNSEngine.search`, the only query
entry point, takes a typed :class:`SearchRequest` and returns a
:class:`SearchResult`.

Searches are FILTERABLE (DESIGN.md §9): ``SearchRequest.filter`` takes
a :class:`repro.core.metadata.Filter` predicate (or one per query of a
batch), compiled host-side against the engine's
:class:`~repro.core.metadata.MetadataStore` into a per-query deny mask
with route-but-don't-return semantics — filtered-out ids still route
the traversal but never enter the returned top-k or a rerank pool, so
filtering changes *which* results return, never how many tier-3
accesses occur. The layer-0 beam widens with filter tightness
(``EngineConfig.filter_ef_cap``).

The index is MUTABLE (DESIGN.md §8): ``engine.add(vectors, texts)``
grows it by incremental HNSW insertion (continuing the offline build's
level stream — no rebuild), ``engine.delete(ids)`` tombstones rows out
of every driver's search, ``engine.upsert(ids, vectors)`` composes the
two under fresh ids; all three return a typed :class:`MutationResult`,
and ``engine.save`` back to the session's directory writes only the
deltas (append-only vector shards + dirtied graph shards + the
tombstone list).

Engine modes (paper §4.2 baselines), validated at config construction:

- ``webanns``       — full system: phased lazy loading + heuristic cache
                      sizing hooks + compiled compute.
- ``webanns-base``  — compiled compute + three-tier cache, but *eager*
                      fetches (every expansion's misses fetched
                      immediately, no lazy list) and no cache optimizer.

(The SIGIR'24 MeMemo baseline — heuristic BFS neighbor prefetch + fixed
cache — is *not* an engine mode: it is its own engine class,
:class:`repro.core.mememo.MememoEngine`.)
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
import uuid as uuid_mod
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq, quant
from repro.core import search as S
from repro.core.graph import HNSWGraph, random_levels
from repro.core.hnsw import build_hnsw, insert_hnsw
from repro.core.index import Index
from repro.core.metadata import Filter, MetadataStore
from repro.core.spans import span, to_host
from repro.core.storage import StorageBackend
from repro.core.store import (
    EVICT_LRU,
    CacheState,
    ExternalStore,
    TieredStore,
    cache_lookup,
    cache_touch,
    fill_union,
)


# Boosted-ef values are snapped UP to this grain: ef_eff is a static
# argument of the phase jits, and the selectivity-driven boost would
# otherwise compile one specialization per observed sel value
# (DESIGN.md §9/§13).
EF_SNAP_GRAIN = 8


def _np_point_distance(
    X: np.ndarray, q: np.ndarray, metric: str
) -> np.ndarray:
    """Host-side exact distances for the rerank pass (numpy so the
    varying candidate-pool shapes never trigger device recompiles)."""
    X = np.asarray(X, np.float32)
    q = np.asarray(q, np.float32)
    if metric == "l2":
        diff = X - q[None, :]
        return np.sum(diff * diff, axis=-1)
    if metric == "ip":
        return -(X @ q)
    if metric == "cos":
        xn = np.linalg.norm(X, axis=-1) + 1e-30
        qn = np.linalg.norm(q) + 1e-30
        return -(X @ q) / (xn * qn)
    raise ValueError(metric)


@dataclasses.dataclass
class QueryStats:
    """Per-query decomposition behind Eq. 2: T = |Q|·t_in_mem + n_db·t_db."""

    n_visited: int = 0  # |Q|: unique items visited on the search path
    n_dist: int = 0  # distance evaluations
    n_hops: int = 0  # beam expansions
    n_db: int = 0  # external accesses during this query
    items_fetched: int = 0
    t_in_mem: float = 0.0  # host+device compute wall time
    t_db: float = 0.0  # modeled external-access time

    @property
    def t_query(self) -> float:
        return self.t_in_mem + self.t_db


@dataclasses.dataclass
class BatchStats:
    """Whole-batch accounting for the batched query driver (DESIGN.md §5).

    ``n_db`` counts actual tier-3 transactions for the batch — ONE per
    phase with any miss, regardless of batch size. Summing the per-query
    ``QueryStats.n_db`` instead would re-count shared fetches; the gap
    between that sum and this field IS the fetch amortization.
    """

    batch_size: int = 0
    n_db: int = 0  # tier-3 accesses for the WHOLE batch
    items_fetched: int = 0  # deduplicated items pulled from tier 3
    n_phases: int = 0  # load phases driven (across layers)
    t_in_mem: float = 0.0
    t_db: float = 0.0

    @property
    def n_db_per_query(self) -> float:
        return self.n_db / max(1, self.batch_size)

    @property
    def t_batch(self) -> float:
        return self.t_in_mem + self.t_db


ENGINE_MODES = ("webanns", "webanns-base")


@dataclasses.dataclass
class EngineConfig:
    mode: str = "webanns"  # one of ENGINE_MODES: 'webanns' | 'webanns-base'
    metric: str = "l2"
    ef_search: int = 64
    ef_upper: int = 1  # beam width on upper layers (HNSW standard: 1)
    cache_capacity: Optional[int] = None  # items; None = dataset size
    eviction: str = "fifo"
    # external-store cost model (see store.ExternalStore)
    t_setup: float = 1.0e-3
    t_per_item: float = 2.0e-6
    simulate_latency: bool = False
    max_phases: int = 10000  # safety bound on a layer's beam phases
    # fused=True runs the WHOLE lazy query (phases + bulk loads + cache
    # updates) as one jitted program (search.lazy_knn_search_fused) with
    # the tier-3 payload device-resident — the TPU-native endpoint;
    # False = host-driven phase loop (the paper's Wasm/JS split).
    fused: bool = False
    # tier-2 slab precision (DESIGN.md §7, §12): 'float32' | 'float16' |
    # 'int8' | 'pq'. Quantized modes hold 2–4x ('pq': 10–30x) more
    # vectors per byte; search runs on dequantized/decoded values, then
    # an exact-rerank pass re-scores the top k·α candidates against
    # full-precision tier-3 vectors (ONE extra access) so recall@k is
    # preserved. rerank_alpha <= 0 disables the rerank (quantized
    # distances returned as-is).
    precision: str = "float32"
    rerank_alpha: float = 2.0
    # PQ geometry (precision='pq' only): number of subspaces M — each
    # cached row is M uint8 codes, so bytes/row = M (DESIGN.md §12).
    # Must divide the vector dimension. The codebook is trained once at
    # session construction (or adopted from a pq artifact) and FROZEN.
    pq_subspaces: int = 8
    # selectivity-adaptive ef boost for filtered search (DESIGN.md §9):
    # with a filter of live selectivity s the layer-0 beam widens to
    # ef_eff = ef * min(filter_ef_cap, sqrt(1/s)) so enough ALLOWED
    # candidates survive route-but-don't-return masking as filters
    # tighten. 1.0 disables the boost (tests use this to pin ef_eff).
    filter_ef_cap: float = 4.0
    # device sharding (DESIGN.md §10): with n_shards > 1 the 'webanns'
    # mode serves searches from the mesh-sharded driver — vector table,
    # tier-2/3 payload, and adjacency row-sharded over a ("shard",) mesh
    # of that many devices, beam phase per shard, candidates merged by
    # the fused cross-shard top-k. Results are bit-identical to the
    # WARMED single-device batched driver (the per-shard slab is 100%
    # resident, so the warm lazy driver is the semantic twin — see
    # tests/test_sharded_parity.py). The 'webanns-base' eager baseline
    # stays single-device.
    n_shards: int = 1

    def __post_init__(self) -> None:
        if self.mode not in ENGINE_MODES:
            raise ValueError(
                f"unknown engine mode {self.mode!r}: expected one of "
                f"{ENGINE_MODES} (the MeMemo baseline is its own engine "
                "class, repro.core.mememo.MememoEngine, not a mode)"
            )
        self.precision = quant.canonical_precision(self.precision)
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.precision == "pq":
            if self.pq_subspaces < 1:
                raise ValueError(
                    f"pq_subspaces must be >= 1, got {self.pq_subspaces}"
                )
            if self.n_shards > 1:
                raise ValueError(
                    "precision='pq' is served by the loop/batched/fused "
                    "drivers; the mesh-sharded driver (n_shards > 1) "
                    "does not carry PQ code slabs yet"
                )


# ----------------------------------------------------- typed session API


@dataclasses.dataclass
class SearchRequest:
    """One search call: a single ``(d,)`` query or a ``(B, d)`` batch.

    ``ef=None`` falls back to ``EngineConfig.ef_search``. ``batch_mode``
    applies to batched requests only: ``'batched'`` is the cross-query
    amortized driver (DESIGN.md §5), ``'loop'`` the sequential fallback.

    ``filter`` restricts results to metadata-matching ids (DESIGN.md
    §9): one :class:`~repro.core.metadata.Filter` (applied to every
    query of a batch) or, for a ``(B, d)`` batch, a length-B sequence of
    per-query ``Optional[Filter]``. Filtering is route-but-don't-return:
    it changes *which* ids return, never the traversal or the number of
    tier-3 accesses at a given effective ef.
    """

    query: np.ndarray
    k: int = 10
    ef: Optional[int] = None
    batch_mode: str = "batched"
    filter: Optional[Union[Filter, Sequence[Optional[Filter]]]] = None


@dataclasses.dataclass
class MutationResult:
    """Typed result of ``add`` / ``delete`` / ``upsert`` (DESIGN.md §8).

    Id rules (the add→delete→add contract, tested): ids are assigned
    monotonically and NEVER reused — a deleted id stays tombstoned
    forever, and an upsert tombstones the old ids and returns fresh
    ones for the replacement rows. ``n_total`` is therefore the size of
    the id space (live + tombstoned), ``n_live`` the rows a search can
    still return.
    """

    ids: np.ndarray  # ids assigned to newly added rows ((k,) int64)
    deleted: np.ndarray  # ids newly tombstoned by this call
    n_live: int
    n_total: int


@dataclasses.dataclass
class SearchResult:
    """Typed result: ids/dists plus the latency decomposition.

    For a single-query request ``stats`` is one :class:`QueryStats`; for
    a batch it is a per-query list and ``batch_stats`` carries the
    whole-batch tier-3 accounting (the amortization truth — see
    :class:`BatchStats`).
    """

    ids: np.ndarray  # (k,) or (B, k)
    dists: np.ndarray  # (k,) or (B, k)
    stats: Union[QueryStats, List[QueryStats]]
    batch_stats: Optional[BatchStats] = None


# --------------------------------------------------------------- jit phases
# The phase loops' programs. Each ends one beam phase of Algorithm 1 and
# returns ``(cache, state)``: the cache is an explicit argument, so each
# traces once per shape, ef and layer width. ``q`` is one (d,) query or
# a (B, d) batch; the batch's phases are vmapped against one shared
# tier-2 snapshot, so its misses are comparable and unionable (§5).


def _end_phase(q, neighbors_l, state: S.SearchState, cache: CacheState,
               metric: str, ef_trigger: int, policy: int):
    """One beam phase against ``cache`` and, under LRU, the touch of the
    beam at the phase boundary (in-phase hits can't touch in-graph)."""
    lookup = lambda ids: cache_lookup(cache, ids)
    phase = S.search_phase if q.ndim == 1 else S.batch_search_phase
    state = phase(q, neighbors_l, state, lookup, metric,
                  ef_trigger=ef_trigger)
    if policy == EVICT_LRU:
        cache = cache_touch(cache, state.beam.ids.reshape(-1))
    return cache, state


@functools.partial(
    jax.jit,
    static_argnames=("ef", "miss_cap", "metric", "ef_trigger", "policy"),
)
def _enter_layer(q, entry_ids, neighbors_l, cache: CacheState, tombs, banned,
                 ef: int, miss_cap: int, metric: str, ef_trigger: int,
                 policy: int):
    """Layer entry: seed a fresh state from ``entry_ids`` (misses go to
    the lazy list) and run the layer's first phase."""
    n = cache.slot_of.shape[0]
    lookup = lambda ids: cache_lookup(cache, ids)
    if q.ndim == 1:
        state = S.seed_state(
            S.make_state(ef, miss_cap, n, tombstones=tombs, banned=banned),
            q, entry_ids, lookup, metric,
        )
    else:
        state = S.batch_seed_state(
            S.batch_make_state(q.shape[0], ef, miss_cap, n,
                               tombstones=tombs, banned=banned),
            q, entry_ids, lookup, metric,
        )
    return _end_phase(q, neighbors_l, state, cache, metric, ef_trigger,
                      policy)


@functools.partial(jax.jit, static_argnames=("metric", "ef_trigger", "policy"))
def _gathered_phase(q, neighbors_l, state: S.SearchState, cache: CacheState,
                    idx, rows, metric: str, ef_trigger: int, policy: int):
    """A gathered phase: fill the misses that ``TieredStore.stage`` /
    ``stage_batch`` fetched (``idx``, ``rows``) into tier 2, merge them
    into the beam and clear the lazy list (Alg. 1 lines 24–31), then run
    the next phase against the filled cache."""
    cache, vecs = fill_union(cache, idx, rows, state.miss_ids.shape,
                             policy=policy)
    load = S.load_phase if q.ndim == 1 else S.batch_load_phase
    state = load(q, state, state.miss_ids, vecs, metric)
    return _end_phase(q, neighbors_l, state, cache, metric, ef_trigger,
                      policy)


@functools.partial(jax.jit, static_argnames=("ef_upper",))
def _descend(beam_ids, entry, walked, n_hops, n_dist, ef_upper: int):
    """Next layer's (1,) or (B, 1) entries, each the first live id of its
    query's ``ef_upper`` best (else its old entry), and the running
    (hops, distances) of the layers walked: the descent reads nothing
    back."""
    best = beam_ids[..., :ef_upper]
    live = best >= 0
    first = jnp.take_along_axis(best, jnp.argmax(live, -1)[..., None], -1)
    return (jnp.where(live.any(-1, keepdims=True), first, entry),
            _tally(walked, n_hops, n_dist))


@jax.jit
def _tally(walked, n_hops, n_dist):
    return walked + jnp.stack([n_hops, n_dist])


@functools.partial(jax.jit, static_argnames=("k",))
def _finalize_cached(state: S.SearchState, k: int):
    return S.finalize_topk(state, k)


class WebANNSEngine:
    """The query session: build / open / save / search over an index.

    ``source`` may be a raw ``(N, d)`` vector array (wrapped in
    :class:`InMemoryBackend` — the seed behavior), any
    :class:`StorageBackend` (e.g. mmap-backed disk shards), or an
    :class:`Index` (in which case ``graph`` must be omitted). The
    session's tier-3 cost model comes from the config and is composed
    onto the backend by :class:`ExternalStore`.
    """

    def __init__(
        self,
        source: Union[np.ndarray, StorageBackend, Index],
        graph: Optional[HNSWGraph] = None,
        config: Optional[EngineConfig] = None,
        texts: Optional[List[str]] = None,
        metadata: Optional[Union[MetadataStore, Dict]] = None,
    ):
        self.config = config or EngineConfig()
        tombstones = None
        level_state = None
        insert_params = None
        self._uuid: Optional[str] = None
        self._last_save_path: Optional[str] = None
        codebook = None
        if isinstance(source, Index):
            if graph is not None:
                raise ValueError(
                    "pass either an Index or (vectors, graph), not both"
                )
            graph = source.graph
            tombstones = source.tombstones
            level_state = source.level_state
            insert_params = source.insert_params
            codebook = source.codebook
            if metadata is None:
                metadata = source.metadata
            self._uuid = source.uuid
            self._last_save_path = (
                os.path.realpath(source.path)
                if source.path is not None else None
            )
            source = source.backend
        if graph is None:
            raise ValueError("an HNSWGraph is required (or pass an Index)")
        self.graph = graph
        # ExternalStore owns the array/backend dispatch + latency wrapping
        self.external = ExternalStore(
            source,
            t_setup=self.config.t_setup,
            t_per_item=self.config.t_per_item,
            simulate_latency=self.config.simulate_latency,
        )
        self.n, self.dim = self.external.n_items, self.external.dim
        # PQ codebook lifecycle (DESIGN.md §12): adopt the artifact's
        # frozen codebook when reopening, else train once here; frozen
        # thereafter — mutations re-encode through it so codes written
        # at different times stay mutually comparable.
        if codebook is None:
            codebook = getattr(self.external.base_backend, "codebook", None)
        self.pq_codebook: Optional[pq.PQCodebook] = None
        if self.config.precision == "pq":
            if codebook is None:
                codebook = pq.train_pq(
                    self.external.vectors,
                    n_subspaces=self.config.pq_subspaces,
                    seed=0,
                )
            self.pq_codebook = codebook
            # an adopted artifact codebook is authoritative over the
            # configured M — keep the budget math consistent with it
            if self.pq_codebook.n_subspaces != self.config.pq_subspaces:
                self.config = dataclasses.replace(
                    self.config,
                    pq_subspaces=self.pq_codebook.n_subspaces,
                )
        cap = self.config.cache_capacity or self.n
        self.store = TieredStore(self.external, cap, self.config.eviction,
                                 precision=self.config.precision,
                                 codebook=self.pq_codebook)
        self._upload_graph()
        # Text-embedding separation (paper §4.1): texts live in a separate
        # id-indexed store, never loaded during queries.
        self.doc_store = DocStore(texts) if texts is not None else None
        # per-id metadata columns (host-resident, consulted only when a
        # Filter compiles to its allow-bitmap — DESIGN.md §9)
        if metadata is not None and not isinstance(metadata, MetadataStore):
            metadata = MetadataStore(metadata, n_rows=self.n)
        self.metadata: Optional[MetadataStore] = metadata
        if self.metadata is not None and self.metadata.n_rows != self.n:
            raise ValueError(
                f"metadata covers {self.metadata.n_rows} ids, backend "
                f"holds {self.n}"
            )
        self._miss_cap = self.config.ef_search + graph.max_degree + 1
        # whole-batch accounting of the last batch search (DESIGN.md §5)
        self.last_batch_stats: Optional[BatchStats] = None
        # ----- mutation lifecycle state (DESIGN.md §8) -----
        # tombstones: (N,) bool — deleted ids; never seeded/expanded/
        # returned by any driver, never reused by add()
        self.tombstones = (
            np.array(tombstones, dtype=bool, copy=True)
            if tombstones is not None else np.zeros(self.n, dtype=bool)
        )
        if self.tombstones.shape[0] != self.n:
            raise ValueError(
                f"tombstone mask covers {self.tombstones.shape[0]} ids, "
                f"backend holds {self.n}"
            )
        self._tombs_dev: Optional[jnp.ndarray] = None
        self._noban_dev: Optional[jnp.ndarray] = None  # (N,) all-False
        # level stream continuation: (seed, draws) such that replaying
        # seed and skipping `draws` uniforms reproduces the next levels
        # the offline build would have sampled. Best-effort (0, n) for
        # bare graphs — exact when constructed via build()/Index.
        self._level_seed, self._levels_drawn = level_state or (0, self.n)
        self._uuid = self._uuid or uuid_mod.uuid4().hex
        # pre-existing graph rows whose links changed since the last
        # save — the rows a delta save must rewrite
        self._dirty_nodes: set = set()
        # insertion hyperparameters: restored from the index artifact
        # (they persist in the manifest next to the level stream — both
        # are needed for grow-by-add parity); build() sets its own args
        self.insert_ef_construction, self.insert_heuristic = (
            insert_params or (200, True)
        )
        if self.tombstones[self.graph.entry_point]:
            self._repair_entry()

    # ----------------------------------------------------------- factory

    @classmethod
    def build(
        cls,
        vectors: np.ndarray,
        M: int = 16,
        ef_construction: int = 200,
        config: Optional[EngineConfig] = None,
        texts: Optional[List[str]] = None,
        seed: int = 0,
        metadata: Optional[Union[MetadataStore, Dict]] = None,
    ) -> "WebANNSEngine":
        config = config or EngineConfig()
        g = build_hnsw(
            vectors, M=M, ef_construction=ef_construction,
            metric=config.metric, seed=seed,
        )
        eng = cls(vectors, g, config, texts, metadata=metadata)
        # exact level-stream state + insertion hyperparameters, so
        # add() continues the offline build bit-for-bit (DESIGN.md §8)
        eng._level_seed, eng._levels_drawn = seed, len(vectors)
        eng.insert_ef_construction = ef_construction
        return eng

    @classmethod
    def from_index(
        cls,
        index: Index,
        config: Optional[EngineConfig] = None,
        texts: Optional[List[str]] = None,
    ) -> "WebANNSEngine":
        """Session over an existing index artifact. The index's metric is
        authoritative — a differing ``config.metric`` is overridden."""
        config = config or EngineConfig(metric=index.metric)
        if config.metric != index.metric:
            config = dataclasses.replace(config, metric=index.metric)
        return cls(index, config=config, texts=texts)

    @classmethod
    def open(
        cls,
        path: str,
        config: Optional[EngineConfig] = None,
        texts: Optional[List[str]] = None,
        mmap: bool = True,
    ) -> "WebANNSEngine":
        """Reopen a saved index: the paper's initialization-stage bulk
        load (one access per shard), graph materialized, vector payload
        left on disk behind :class:`ShardedFileBackend`. No HNSW rebuild.
        """
        return cls.from_index(Index.load(path, mmap=mmap), config, texts)

    def save(
        self,
        path: str,
        shard_bytes: int = 64 * 1024 * 1024,
        precision: Optional[str] = None,
    ) -> dict:
        """Persist this session's index (graph + vectors + tombstones).

        When ``path`` is the directory this session was opened from (or
        last saved to), only the MUTATIONS since then are written —
        append-only vector delta shards, dirtied neighbor shards, the
        tombstone list, and a manifest merge bumping ``mutation_epoch``
        (DESIGN.md §8). Any other target gets a full save (delta saves
        never span lineages). Returns the save witness
        ``{"mode", "bytes_written", "epoch"}``.

        ``precision=None`` follows the session's configured precision,
        so an int8 session persists int8 shards end-to-end (~4× smaller
        payload). Note the trade: a session reopened over int8 shards
        serves DEQUANTIZED tier 3, so the exact-rerank pass is exact
        only w.r.t. that lossy payload (see ``_rerank_exact``). Pass
        ``"float32"`` explicitly to keep the payload full-precision on
        disk regardless of the cache mode. A precision differing from
        the existing directory's codec also forces a full save.
        """
        idx = self.index
        # compare REAL paths: "./idx", an absolute spelling, or a
        # symlink of the session's directory must all stay in-lineage
        # (a raw string mismatch would force a full rewrite)
        if os.path.realpath(path) != self._last_save_path:
            idx.uuid = None  # new lineage for a new target directory
        info = idx.save(path, shard_bytes=shard_bytes,
                        precision=precision or self.config.precision,
                        dirty_nodes=self._dirty_nodes)
        self._uuid = idx.uuid
        self._last_save_path = os.path.realpath(path)
        self._dirty_nodes = set()
        return info

    @property
    def index(self) -> Index:
        """The session's index artifact (graph + storage + tombstones)."""
        return Index(
            graph=self.graph,
            backend=self.external.base_backend,
            path=self._last_save_path,
            tombstones=self.tombstones,
            uuid=self._uuid,
            level_state=(self._level_seed, self._levels_drawn),
            insert_params=(
                self.insert_ef_construction, self.insert_heuristic
            ),
            metadata=self.metadata,
            codebook=self.pq_codebook,
        )

    # --------------------------------------------------- mutation lifecycle

    @property
    def n_live(self) -> int:
        """Rows a search can still return (total minus tombstoned)."""
        return self.n - int(self.tombstones.sum())

    def _repair_entry(self) -> None:
        """Move the HNSW entry point to a live node (the highest-level
        one, as the offline build would pick). Called whenever a delete
        or upsert tombstones the current entry."""
        live = np.nonzero(~self.tombstones)[0]
        if live.size == 0:
            return  # empty engine: searches short-circuit to -1 results
        self.graph.entry_point = int(live[np.argmax(self.graph.levels[live])])

    def _tombs_device(self) -> jnp.ndarray:
        if self._tombs_dev is None:
            self._tombs_dev = jnp.asarray(self.tombstones)
        return self._tombs_dev

    def _invalidate_device_state(self, table: bool) -> None:
        """Drop cached device arrays after a mutation. ``table=True``
        also drops the fused driver's device-resident tier-3 payload
        (required after add/upsert; deletes only touch the mask)."""
        self._tombs_dev = None
        self._noban_dev = None
        # the mesh-sharded state bakes in tombstones AND the payload/
        # adjacency, so any mutation invalidates it (DESIGN.md §10)
        self._shard_rt = None
        if table:
            for attr in ("_table_dev", "_tscales_dev", "_tcodebook_dev"):
                if hasattr(self, attr):
                    delattr(self, attr)

    # ------------------------------------------------------ filtered search

    def _noban_device(self) -> jnp.ndarray:
        """Cached all-False deny mask for unfiltered requests, so the
        no-filter path pays one device constant, not one per query."""
        if self._noban_dev is None:
            self._noban_dev = jnp.zeros((self.n,), bool)
        return self._noban_dev

    def _compile_filter(self, filt: Filter) -> Tuple[np.ndarray, float]:
        """Compile one predicate to (deny mask, live selectivity).

        The allow-bitmap is evaluated host-side against the metadata
        columns — metadata is never fetched from tier 3, so compiling a
        filter costs ZERO external accesses. Selectivity is measured
        over the LIVE (non-tombstoned) id space: it drives the ef boost
        and the empty-result short-circuit.
        """
        if not isinstance(filt, Filter):
            raise TypeError(
                f"SearchRequest.filter must be a Filter (or a sequence "
                f"of them for a batch), got {type(filt).__name__}"
            )
        allow = np.asarray(filt.mask(self.metadata), bool)
        if allow.shape != (self.n,):
            raise ValueError(
                f"filter mask covers {allow.shape[0]} ids, index holds "
                f"{self.n}"
            )
        live_allowed = int((allow & ~self.tombstones).sum())
        sel = live_allowed / max(1, self.n_live)
        return ~allow, sel

    def _boost_ef(self, ef: int, sel: float) -> int:
        """Selectivity-adaptive beam widening: ef_eff = ef * min(cap,
        sqrt(1/sel)), so recall holds as filters tighten while the cap
        bounds the latency cost (DESIGN.md §9).

        The boosted ef is snapped UP to ``EF_SNAP_GRAIN`` — sel is a
        continuous runtime quantity, and every distinct ef_eff value is
        a distinct static argument of the phase jits, so an unsnapped
        boost compiles one phase specialization per observed selectivity
        (the R003 retrace-hazard class; see DESIGN.md §13)."""
        if sel >= 1.0:
            return ef
        boost = min(self.config.filter_ef_cap,
                    math.sqrt(1.0 / max(sel, 1e-9)))
        eff = int(math.ceil(ef * max(1.0, boost)))
        eff += (-eff) % EF_SNAP_GRAIN  # snap UP: wider beam only helps
        return min(self.n, eff)

    def add(
        self,
        vectors: np.ndarray,
        texts: Optional[List[str]] = None,
        metadata: Optional[Dict] = None,
    ) -> MutationResult:
        """Insert new vectors into the LIVE index — no rebuild.

        Levels are sampled by continuing the offline build's RNG stream,
        and the insertion loop is the same one ``build_hnsw`` runs, so
        an index grown by ``add`` is bit-identical to a fresh build over
        the concatenated corpus (when no deletes intervene; tested).
        New ids are assigned monotonically from ``n_total`` — deleted
        ids are never reused. Tombstoned nodes are excluded from link
        selection, and the mutated rows are tracked for delta saves.

        ``metadata`` maps column name → one value per added vector;
        the store grows in lockstep with the id space (existing columns
        a row omits get their kind's fill value, previously-unseen
        columns are backfilled — DESIGN.md §9).
        """
        vectors = np.atleast_2d(np.asarray(vectors, dtype=np.float32))
        if vectors.shape[0] == 0:
            return MutationResult(
                ids=np.empty(0, np.int64), deleted=np.empty(0, np.int64),
                n_live=self.n_live, n_total=self.n,
            )
        if vectors.shape[1] != self.dim:
            raise ValueError(
                f"added vectors have dim {vectors.shape[1]}, index holds "
                f"dim {self.dim}"
            )
        if texts is not None and len(texts) != vectors.shape[0]:
            raise ValueError(
                f"{len(texts)} texts for {vectors.shape[0]} vectors"
            )
        if metadata is not None and self.metadata is None:
            # creating the (still-empty) store pre-mutation is safe: it
            # stays consistent with n even if a later step raises
            self.metadata = MetadataStore(n_rows=self.n)
        if self.metadata is not None:
            # full dry-run validation (names, lengths, kinds, dtypes)
            # BEFORE anything is committed — a bad metadata dict must
            # never leave the store out of sync with the id space
            self.metadata.validate_extend(vectors.shape[0], metadata)
        n_new = vectors.shape[0]
        restart = self.n_live == 0  # dead graph: re-seed the entry point
        # 1) payload append (tier 3 wraps itself in a DeltaBackend)
        new_ids = self.external.append(vectors)
        # 2) continue the build-time level stream. PCG64.advance is an
        # O(1) skip-ahead to the same stream position that generating
        # (and discarding) the prior draws would reach — one 64-bit
        # state step per double, so `advance(draws)` lands exactly
        # where random(draws) would (asserted in test_mutation.py)
        bitgen = np.random.PCG64(self._level_seed)
        if self._levels_drawn:
            bitgen.advance(self._levels_drawn)
        levels_new = random_levels(n_new, self.graph.M,
                                   np.random.Generator(bitgen))
        self._levels_drawn += n_new
        # 3) incremental HNSW insertion with bidirectional link repair
        exclude = None
        if self.tombstones.any():
            exclude = np.concatenate(
                [self.tombstones, np.zeros(n_new, dtype=bool)]
            )
        self.graph, dirty = insert_hnsw(
            self.graph, self.external.vectors, new_ids, levels_new,
            ef_construction=self.insert_ef_construction,
            heuristic=self.insert_heuristic, exclude=exclude,
            restart_entry=restart,
        )
        self._dirty_nodes |= dirty
        # 4) grow per-id engine state: tombstone mask, tier-2 id space,
        #    device-resident graph; drop stale device caches
        self.tombstones = np.concatenate(
            [self.tombstones, np.zeros(n_new, dtype=bool)]
        )
        self.n = self.external.n_items
        self._upload_graph()
        self.store.grow(self.n)
        if texts is not None and self.doc_store is None:
            self.doc_store = DocStore([None] * (self.n - n_new))
        if self.doc_store is not None:
            self.doc_store.extend(
                texts if texts is not None else [None] * n_new
            )
        if self.metadata is not None:
            self.metadata.extend(n_new, metadata)  # pre-validated above
        self._invalidate_device_state(table=True)
        if self.tombstones[self.graph.entry_point]:
            self._repair_entry()
        return MutationResult(
            ids=new_ids, deleted=np.empty(0, np.int64),
            n_live=self.n_live, n_total=self.n,
        )

    def delete(self, ids: Union[int, Sequence[int]]) -> MutationResult:
        """Tombstone ``ids``: they are immediately evicted from tier 2
        and masked out of every driver's search (never seeded, expanded,
        or returned — see ``search.make_state``). The graph keeps its
        structure — deletes are O(k) mask writes, and the live nodes'
        construction-time topology is untouched. Tombstoned rows keep
        their payload bytes (ids are never reused); reclaiming them
        means rebuilding into a fresh index, the classic compaction
        trade. Deleting an already-tombstoned id is a no-op.
        """
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        if ids.size and (ids.min() < 0 or ids.max() >= self.n):
            raise ValueError(
                f"delete ids out of range [0, {self.n}): "
                f"{ids[(ids < 0) | (ids >= self.n)][:4]}…"
            )
        fresh = np.unique(ids[~self.tombstones[ids]])
        self.tombstones[fresh] = True
        if fresh.size:
            self.store.invalidate(fresh)
            self._invalidate_device_state(table=False)
            if self.tombstones[self.graph.entry_point]:
                self._repair_entry()
        return MutationResult(
            ids=np.empty(0, np.int64), deleted=fresh,
            n_live=self.n_live, n_total=self.n,
        )

    def upsert(
        self,
        ids: Union[int, Sequence[int]],
        vectors: np.ndarray,
        texts: Optional[List[str]] = None,
        metadata: Optional[Dict] = None,
    ) -> MutationResult:
        """Replace rows: tombstone ``ids`` and insert ``vectors`` as
        fresh rows. Ids are NEVER reused, so the replacements come back
        under new ids (``result.ids``, aligned with ``vectors``;
        ``result.deleted`` holds the retired ones). This keeps vector
        shards append-only — an upsert costs one delta shard plus a
        tombstone entry, never a rewrite.
        """
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        vectors = np.atleast_2d(np.asarray(vectors, dtype=np.float32))
        if len(ids) != vectors.shape[0]:
            raise ValueError(
                f"upsert replaces {len(ids)} ids with "
                f"{vectors.shape[0]} vectors — counts must match"
            )
        # validate EVERYTHING add() would reject before tombstoning
        # anything: ids are never reused, so a delete that precedes a
        # failed add would silently lose the old rows forever
        if vectors.shape[1] != self.dim:
            raise ValueError(
                f"upserted vectors have dim {vectors.shape[1]}, index "
                f"holds dim {self.dim}"
            )
        if texts is not None and len(texts) != vectors.shape[0]:
            raise ValueError(
                f"{len(texts)} texts for {vectors.shape[0]} vectors"
            )
        if metadata is None and self.metadata is not None:
            # replacements inherit the retired rows' metadata unless the
            # caller overrides it — an upsert must not silently drop a
            # document out of every filtered view
            metadata = {
                name: col[ids]
                for name, col in self.metadata.to_columns().items()
            }
        if metadata is not None:
            # metadata failures must also surface BEFORE the delete
            (self.metadata or MetadataStore(n_rows=self.n)) \
                .validate_extend(vectors.shape[0], metadata)
        deleted = self.delete(ids).deleted
        added = self.add(vectors, texts=texts, metadata=metadata)
        return MutationResult(
            ids=added.ids, deleted=deleted,
            n_live=self.n_live, n_total=self.n,
        )

    # ------------------------------------------------------------ sizing

    def resize_cache(self, capacity: int, warm: bool = False) -> None:
        """Re-initialize tier 2 at ``capacity`` items. ``warm=True``
        immediately re-populates it (uncounted init-stage load) — the
        hook the cross-tenant allocator uses so a reallocation never
        serves its first queries from an artificially cold cache."""
        self.store.resize(int(capacity))
        if warm:
            self.warm_cache()

    def resize_cache_bytes(self, budget_bytes: int, warm: bool = False) -> int:
        """Resize tier 2 to the largest capacity fitting ``budget_bytes``
        at the session's precision (DESIGN.md §7/§11). Returns the item
        capacity actually applied."""
        cap = max(1, quant.capacity_for_budget(
            int(budget_bytes), self.dim, self.config.precision,
            n_subspaces=(self.pq_codebook.n_subspaces
                         if self.pq_codebook is not None else None),
        ))
        cap = min(cap, self.n)
        self.resize_cache(cap, warm=warm)
        return cap

    # ------------------------------------------------ per-tenant stats

    @property
    def access_stats(self):
        """The live tier-3 :class:`~repro.core.store.AccessStats` — the
        counters the session manager samples per tenant (DESIGN.md §11)."""
        return self.external.stats

    def snapshot_access_stats(self) -> dict:
        """A plain-dict snapshot of the tier-3 counters, safe to diff
        across calls (the manager attributes the delta between two
        snapshots to whichever tenant's operation ran in between)."""
        s = self.external.stats
        return {
            "n_db": s.n_db,
            "items_fetched": s.items_fetched,
            "items_used": s.items_used,
            "modeled_time": s.modeled_time,
            "tier2_hits": s.tier2_hits,
            "tier2_misses": s.tier2_misses,
            "host_syncs": s.host_syncs,
        }

    def warm_cache(self, ids: Optional[np.ndarray] = None) -> None:
        if ids is None:
            ids = np.arange(min(self.store.capacity, self.n))
        ids = np.asarray(ids)
        ids = ids[~self.tombstones[ids]]  # never stage tombstoned rows
        if len(ids):
            self.store.warm(ids)

    def cache_bytes(self) -> int:
        """Resident tier-2 bytes at the configured precision — the byte
        budget the cache-size optimizer trades against capacity (§7)."""
        return self.store.cache_bytes()

    # -------------------------------------------------------- exact rerank

    def _rerank_active(self) -> bool:
        cfg = self.config
        return cfg.precision != "float32" and cfg.rerank_alpha > 0

    def _rerank_exact(
        self, Q: np.ndarray, ids: np.ndarray, dists: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact-rerank pass (DESIGN.md §7): re-score B candidate pools
        ((B, d) queries, (B, m) pools; one query as a batch of one)
        against full-precision tier-3 vectors in ONE counted access.

        The beam's distances were computed on dequantized tier-2 rows;
        the pools (top k·α of each beam) are unioned, deduplicated and
        re-fetched from tier 3 — bypassing the quantized cache — and
        exactly re-scored, so the returned top-k order/distances match
        what a float32 cache would have produced whenever the true k-th
        neighbor is inside the pool. Quantized beam distances are kept
        only when no pool holds a valid row.

        "Full precision" means tier 3's *stored* precision: if the index
        itself was persisted with ``save(precision="int8")``, fetches
        serve dequantized int8 and the rerank is exact w.r.t. that lossy
        payload, not the original corpus (keep float32 shards —
        ``save(precision="float32")`` — when tier-3 fidelity matters).
        """
        ids = np.asarray(ids)
        dists = np.asarray(dists)
        B, m = ids.shape
        valid = ids >= 0
        if not valid.any():
            return ids[:, :k], dists[:, :k]
        with span("rerank"):
            union = np.unique(ids[valid])  # sorted — searchsorted below
            fetched = self.external.fetch(union)
            self.external.mark_used_ids(union)
            exact = np.full((B, m), np.inf, np.float32)
            # rows/qidx are in ids[valid]'s row-major order, so per-row
            # distances scatter back through one flat buffer
            rows = fetched[np.searchsorted(union, ids[valid])]
            qidx = np.broadcast_to(np.arange(B)[:, None], (B, m))[valid]
            flat = np.empty(rows.shape[0], np.float32)
            for b in range(B):
                sel = qidx == b
                if sel.any():
                    flat[sel] = _np_point_distance(
                        rows[sel], Q[b], self.config.metric
                    )
            exact[valid] = flat
            order = np.argsort(exact, axis=1, kind="stable")
            return (np.take_along_axis(ids, order, 1)[:, :k],
                    np.take_along_axis(exact, order, 1)[:, :k])

    # ------------------------------------------------------------- query

    def _upload_graph(self) -> None:
        """The device's copy of the graph, whole (fused and sharded
        drivers) and one array per layer (the phase loops), so that no
        phase slices a layer out eagerly."""
        self.neighbors = jnp.asarray(self.graph.neighbors)
        self._layer_neighbors = [
            jnp.asarray(nb) for nb in self.graph.neighbors
        ]

    def _lazy_layer(
        self, q: jnp.ndarray, layer: int, entry: jnp.ndarray, ef: int,
        demand: Tuple[BatchStats, List[QueryStats]], eager: bool,
        banned: Optional[jnp.ndarray] = None,
    ) -> S.SearchState:
        """Run one layer with phased lazy loading (or eager fetches) for a
        (d,) query or a (B, d) batch, from the device-resident (1,) or
        (B, 1) ``entry``: one program and one read of the whole miss list
        per phase, and ONE tier-3 access per phase for every list
        (DESIGN.md §5). ``demand`` is the request's ``(BatchStats,
        [QueryStats])``: each access, and each query's share of it."""
        cfg = self.config
        acc = self.external.stats
        store = self.store
        batch, queries = demand
        # a batch's lists go as one sorted union in a power-of-two bucket;
        # one query's list keeps its order at its fixed length (§5)
        stage = store.stage if q.ndim == 1 else store.stage_batch
        miss_cap = ef + self.graph.max_degree + 1
        # eager mode (webanns-base): trigger=1 → flush L after every miss
        trigger = 1 if eager else ef
        neighbors_l = self._layer_neighbors[layer]
        t0 = time.perf_counter()
        with span("seed"):
            # an (N,) mask is broadcast over a batch inside the program
            store.cache, state = _enter_layer(
                q, entry, neighbors_l, store.cache, self._tombs_device(),
                self._noban_device() if banned is None else banned,
                ef, miss_cap, cfg.metric, trigger, store.eviction,
            )
            miss_np = to_host(state.miss_ids, acc)
        batch.t_in_mem += time.perf_counter() - t0
        for phase in range(1, cfg.max_phases + 1):  # 1 ran in the entry
            # each list holds its misses first and -1 after them
            mc = np.atleast_1d((miss_np >= 0).sum(-1))
            n_miss = int(mc.sum())
            acc.tier2_misses += n_miss
            if n_miss == 0 or phase == cfg.max_phases:
                break
            # ONE tier-3 access for every list (Alg. 1 line 24); the
            # lists go whole, so no program is sized by the miss count
            with span("tier2_gather"):
                db0, fetched0 = acc.n_db, acc.items_fetched
                idx, rows = stage(miss_np)
            batch.n_db += acc.n_db - db0
            batch.items_fetched += acc.items_fetched - fetched0
            batch.n_phases += 1
            for b in np.nonzero(mc)[0]:
                queries[b].n_db += 1
                queries[b].items_fetched += int(mc[b])
            t0 = time.perf_counter()
            with span("beam_phase"):
                store.cache, state = _gathered_phase(
                    q, neighbors_l, state, store.cache, idx, rows,
                    cfg.metric, trigger, store.eviction,
                )
                miss_np = to_host(state.miss_ids, acc)
            batch.t_in_mem += time.perf_counter() - t0
        return state

    def _query_fused(
        self, q: np.ndarray, k: int, ef: int,
        banned: Optional[jnp.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, QueryStats]:
        """Fused single-query driver: the whole lazy query is one device
        program, so the host phase loop's counters (``tier2_hits``,
        ``tier2_misses``, ``host_syncs``) do not move."""
        cfg = self.config
        stats = QueryStats()
        if not hasattr(self, "_table_dev"):
            # quantized modes keep the device-resident tier-3 payload
            # QUANTIZED (~4x less device memory); the fused program
            # dequantizes inside the bulk-load gather (DESIGN.md §7)
            if cfg.precision == "pq":
                # DRAM-free mode (§12): the device table is (N, M) uint8
                # codes + the shared codebook — NO f32/int8 vector slab
                # exists on device; the fused program decodes inside the
                # bulk-load gather (ADC by the subspace decomposition)
                self._table_dev = jnp.asarray(pq.encode_np(
                    self.external.vectors, self.pq_codebook.centroids
                ))
                self._tscales_dev = None
                self._tcodebook_dev = jnp.asarray(
                    self.pq_codebook.centroids, jnp.float32
                )
            elif cfg.precision != "float32":
                payload, scales = quant.quantize_np(
                    self.external.vectors, cfg.precision
                )
                self._table_dev = jnp.asarray(payload)
                self._tscales_dev = (
                    jnp.asarray(scales) if cfg.precision == "int8" else None
                )
            else:
                self._table_dev = jnp.asarray(self.external.vectors)
                self._tscales_dev = None
        # quantized modes: run the fused program for the rerank POOL so
        # the host-side exact pass has k·α candidates to re-score
        k_run = k
        if self._rerank_active():
            k_run = min(max(ef, k), quant.rerank_pool(k, cfg.rerank_alpha))
        t0 = time.perf_counter()
        dists, ids, (n_db, n_fetch), cache = S.lazy_knn_search_fused(
            jnp.asarray(q, jnp.float32), self._table_dev, self.neighbors,
            jnp.asarray(self.graph.entry_point, jnp.int32),
            self.store.cache, k=k_run, ef=ef, metric=cfg.metric,
            eviction=self.store.eviction, table_scales=self._tscales_dev,
            tombstones=self._tombs_device(), banned=banned,
            table_codebook=getattr(self, "_tcodebook_dev", None),
        )
        ids.block_until_ready()
        stats.t_in_mem = time.perf_counter() - t0
        self.store.cache = cache
        stats.n_db = int(n_db)
        stats.items_fetched = int(n_fetch)
        # apply the external-access cost model analytically
        stats.t_db = stats.n_db * cfg.t_setup \
            + stats.items_fetched * cfg.t_per_item
        acc = self.external.stats
        acc.n_db += stats.n_db
        acc.items_fetched += stats.items_fetched
        acc.items_used += stats.items_fetched  # lazy: R=0
        acc.modeled_time += stats.t_db
        stats.n_visited = stats.items_fetched  # lower bound (hits uncounted)
        if self._rerank_active():
            db0, f0, m0 = acc.n_db, acc.items_fetched, acc.modeled_time
            ids_np, dists_np = self._rerank_exact(
                np.asarray(q)[None], np.asarray(ids)[None],
                np.asarray(dists)[None], k,
            )
            stats.n_db += acc.n_db - db0
            stats.items_fetched += acc.items_fetched - f0
            stats.t_db += acc.modeled_time - m0
            return ids_np[0], dists_np[0], stats
        return np.asarray(ids), np.asarray(dists), stats

    def _search_one(
        self, q: np.ndarray, k: int, ef: Optional[int],
        filt: Optional[Filter] = None,
        boost: bool = True,
    ) -> Tuple[np.ndarray, np.ndarray, QueryStats]:
        """One-query front end. Returns (ids, dists, stats).

        ``filt`` restricts results via route-but-don't-return masking
        (DESIGN.md §9): traversal is IDENTICAL to an unfiltered run at
        the same effective ef (so filtering adds zero tier-3 accesses);
        banned ids are dropped only at top-k extraction and from the
        exact-rerank pool. The effective ef widens with the filter's
        live selectivity (``_boost_ef``).
        """
        cfg = self.config
        ef = ef or cfg.ef_search
        if self.n_live == 0:  # fully-tombstoned engine: nothing to return
            return (np.full(k, -1, np.int32),
                    np.full(k, np.inf, np.float32), QueryStats())
        banned = None
        if filt is not None:
            banned_np, sel = self._compile_filter(filt)
            if sel <= 0.0:  # nothing can match: skip the search entirely
                return (np.full(k, -1, np.int32),
                        np.full(k, np.inf, np.float32), QueryStats())
            if boost:  # batch callers pre-boost to the shared ef_eff
                ef = self._boost_ef(ef, sel)
            banned = jnp.asarray(banned_np)
        if cfg.fused and cfg.mode == "webanns":
            return self._query_fused(q, k, ef, banned=banned)
        ids, dists, (stats,), _ = self._lazy_search(q, k, ef, banned)
        return ids, dists, stats

    def _lazy_search(
        self, q: np.ndarray, k: int, ef: int,
        banned: Optional[jnp.ndarray],
    ) -> Tuple[np.ndarray, np.ndarray, List[QueryStats], BatchStats]:
        """The phase-loop search of a (d,) query or a (B, d) batch
        (DESIGN.md §5) → (ids, dists, [QueryStats], BatchStats). The
        entries and the counts stay on the device between layers: a
        layer costs its phases alone, however many layers the graph
        drew, and the counts are read once, in finalize."""
        cfg = self.config
        eager = cfg.mode == "webanns-base"
        acc = self.external.stats
        misses0, t_db0 = acc.tier2_misses, acc.modeled_time
        lead = q.shape[:-1]  # () for one query, (B,) for a batch
        B = int(np.prod(lead))
        demand = (BatchStats(batch_size=B), [QueryStats() for _ in range(B)])
        batch, queries = demand
        # the query, entries and counts go up as copies, not programs
        qj = jnp.asarray(q)
        entry = jnp.asarray(np.full(lead + (1,), self.graph.entry_point,
                                    np.int32))
        walked = jnp.asarray(np.zeros((2,) + lead, np.int32))  # hops, dists
        # upper layers: beam of ef_upper (greedy for 1), lazily loaded too;
        # the deny mask is irrelevant here (descent only routes)
        for lc in range(self.graph.max_level, 0, -1):
            st = self._lazy_layer(qj, lc, entry, cfg.ef_upper, demand, eager)
            with span("descend"):
                entry, walked = _descend(
                    st.beam.ids, entry, walked, st.n_hops, st.n_dist,
                    cfg.ef_upper,
                )
        st = self._lazy_layer(
            qj, 0, entry, max(ef, k), demand, eager, banned=banned
        )
        with span("finalize"):
            ids, dists = self._finalize(q, st, walked, k, banned is not None,
                                        demand, t_db0)
        acc.tier2_hits += sum(s.n_dist for s in queries) \
            - (acc.tier2_misses - misses0)
        return ids, dists, queries, batch

    def _finalize(
        self, q: np.ndarray, state: S.SearchState, walked: jnp.ndarray,
        k: int, filtered: bool,
        demand: Tuple[BatchStats, List[QueryStats]], t_db0: float,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """The reads that end the search of a (d,) query or a (B, d)
        batch: each query's (hops, distances) over every layer, in one
        read, then its top-k on the host. A quantized search reranks
        each beam's head exactly, from an allowed-only pool under a
        filter, so a banned id never reaches the rerank fetch (§9); a
        filtered one re-extracts the allowed top-k. The rerank's access
        is the request's; each query of a batch demanded it, and a single
        query is charged what it fetched. ``demand`` then takes the
        modeled tier-3 time since ``t_db0``, and each query its share.
        """
        cfg = self.config
        acc = self.external.stats
        batch, queries = demand
        counts = to_host(_tally(walked, state.n_hops, state.n_dist), acc)
        if self._rerank_active():
            pool = min(state.beam.ids.shape[-1],
                       quant.rerank_pool(k, cfg.rerank_alpha))
            if filtered:
                p_dists, p_ids = _finalize_cached(state, pool)  # lint: disable=R003 -- pool ≤ k·α with the beam width grain-snapped in _boost_ef; bounded trace set
            else:
                p_ids = state.beam.ids[..., :pool]
                p_dists = state.beam.dists[..., :pool]
            db0, f0 = acc.n_db, acc.items_fetched
            ids, dists = self._rerank_exact(
                q.reshape(-1, q.shape[-1]),
                to_host(p_ids, acc).reshape(-1, pool),
                to_host(p_dists, acc).reshape(-1, pool), k,
            )
            ids = ids.reshape(q.shape[:-1] + (-1,))
            dists = dists.reshape(q.shape[:-1] + (-1,))
            batch.n_db += acc.n_db - db0
            batch.items_fetched += acc.items_fetched - f0
            if q.ndim == 1:
                queries[0].n_db += acc.n_db - db0
                queries[0].items_fetched += acc.items_fetched - f0
            else:
                for s in queries:
                    s.n_db += 1
        elif filtered:
            f_dists, f_ids = _finalize_cached(state, k)
            ids, dists = to_host(f_ids, acc), to_host(f_dists, acc)
        else:
            ids = to_host(state.beam.ids[..., :k], acc)
            dists = to_host(state.beam.dists[..., :k], acc)
        batch.t_db = acc.modeled_time - t_db0
        for s, hops, n_dist in zip(queries, *counts.reshape(2, -1)):
            s.n_hops += int(hops)
            s.n_dist += int(n_dist)
            s.n_visited = s.n_dist  # every visited id gets a distance
            s.t_in_mem = batch.t_in_mem / len(queries)
            s.t_db = batch.t_db / len(queries)
        return ids, dists

    def _batch_filter(
        self, filt, B: int, ef: int
    ) -> Tuple[Optional[List[Optional[Filter]]], Optional[jnp.ndarray], int]:
        """A batch's request filter → (one Filter or None per query, the
        deny mask on the device, the batch's one effective ef); with no
        filter, ``(None, None, ef)``.

        A broadcast Filter compiles ONCE to an (N,) mask, which the
        programs broadcast over the batch (no (B, N) host copy);
        per-query filters compile to a (B, N) matrix whose unfiltered
        rows deny nothing. The batch shares ONE effective ef (a jitted
        phase has one static beam width), so the widest per-query boost
        wins — every batch driver uses it, keeping loop/batched parity
        exact (DESIGN.md §9).
        """
        if filt is None:
            return None, None, ef
        if isinstance(filt, Filter):
            filters, compiled = [filt] * B, [self._compile_filter(filt)]
        else:
            filters = list(filt)
            if len(filters) != B:
                raise ValueError(
                    f"{len(filters)} filters for a batch of {B} queries — "
                    "pass one Filter (broadcast) or exactly one per query"
                )
            if all(f is None for f in filters):
                return None, None, ef
            compiled = [None if f is None else self._compile_filter(f)
                        for f in filters]
        ef_eff = ef
        for c in compiled:  # (deny mask, live selectivity) or None
            if c is not None and c[1] > 0.0:
                ef_eff = max(ef_eff, self._boost_ef(ef, c[1]))
        if isinstance(filt, Filter):
            return filters, jnp.asarray(compiled[0][0]), ef_eff
        mask = np.zeros((B, self.n), bool)
        for b, c in enumerate(compiled):
            if c is not None:
                mask[b] = c[0]
        return filters, jnp.asarray(mask), ef_eff

    # ------------------------------------------- mesh-sharded driver (§10)

    def _shard_runtime(self):
        """(mesh, ShardedEngineState) for ``config.n_shards`` devices —
        built lazily on first sharded search, dropped by ANY mutation
        (``_invalidate_device_state``: payload, adjacency, and tombstone
        mask are all baked into the sharded state)."""
        rt = getattr(self, "_shard_rt", None)
        if rt is None:
            from repro.core import distributed as dshard
            from repro.launch.mesh import make_shard_mesh

            mesh = make_shard_mesh(self.config.n_shards)
            state = dshard.build_sharded_engine_state(
                self.external.base_backend,
                np.asarray(self.graph.neighbors),
                self.tombstones,
                mesh,
                precision=self.config.precision,
                metric=self.config.metric,
            )
            self._shard_rt = rt = (mesh, state)
        return rt

    def _sharded_layer(self, Qj, layer: int, entry: jnp.ndarray, ef: int):
        """One layer as one shard_map program → (beam ids/dists/explored,
        n_hops, n_dist), all replicated (B, ...) arrays."""
        from repro.core import distributed as dshard

        mesh, st = self._shard_runtime()
        prog = dshard.sharded_layer_program(
            mesh, ef, self.config.metric, st.precision == "int8"
        )
        return prog(
            Qj, jnp.asarray(entry), st.table, st.scales,
            st.neighbors[:, layer], st.tombstones,
        )

    def _sharded_many(
        self, Q: np.ndarray, k: int, ef: int,
        banned: Optional[jnp.ndarray],
    ) -> Tuple[np.ndarray, np.ndarray, List[QueryStats]]:
        """Mesh-sharded batch driver body (DESIGN.md §10).

        Every layer runs as ONE shard_map program — beam phase per shard
        against its device-resident rows, candidates merged by the fused
        cross-shard top-k — while the descent, the deny mask, finalize
        and the exact rerank are the phase loops' own, so (ids, dists)
        come back bit-identical to the batched driver run WARM (each
        shard's slab is 100% resident; a cold lazy driver's expansion
        order is cache-state-dependent — see the protocol in
        tests/test_sharded_parity.py). Traversal performs ZERO tier-3
        accesses; only the exact-rerank pass fetches. With no host phase
        loop, ``tier2_hits`` and ``tier2_misses`` do not move;
        ``host_syncs`` counts finalize's reads.
        """
        cfg = self.config
        B = len(Q)
        demand = (BatchStats(batch_size=B), [QueryStats() for _ in range(B)])
        bstats, per_stats = demand
        Qj = jnp.asarray(Q)
        t_db0 = self.external.stats.modeled_time
        entry = jnp.asarray(np.full((B, 1), self.graph.entry_point, np.int32))
        walked = jnp.asarray(np.zeros((2, B), np.int32))  # hops, dists
        t0 = time.perf_counter()
        for lc in range(self.graph.max_level, 0, -1):
            bi, _, _, hops, n_dist = self._sharded_layer(
                Qj, lc, entry, cfg.ef_upper
            )
            entry, walked = _descend(bi, entry, walked, hops, n_dist,
                                     cfg.ef_upper)
        bi, bd, be, hops, n_dist = self._sharded_layer(
            Qj, 0, entry, max(ef, k)
        )
        bi.block_until_ready()
        bstats.t_in_mem = time.perf_counter() - t0
        # adapt the final beam to the finalize plumbing shared with the
        # single-device drivers (only beam, banned and counts are read)
        st = S.SearchState(
            beam=S.Beam(ids=bi, dists=bd, explored=be),
            visited=jnp.zeros((1, 1), bool),
            banned=jnp.broadcast_to(
                self._noban_device() if banned is None else banned,
                (B, self.n),
            ),
            miss_ids=jnp.zeros((1, 1), jnp.int32),
            miss_count=jnp.zeros((1,), jnp.int32),
            n_hops=hops,
            n_dist=n_dist,
        )
        ids, dists = self._finalize(Q, st, walked, k, banned is not None,
                                    demand, t_db0)
        self.last_batch_stats = bstats
        return ids, dists, per_stats

    def _search_many(
        self, Q: np.ndarray, k: int, ef: Optional[int], batch_mode: str,
        filt=None,
    ) -> Tuple[np.ndarray, np.ndarray, List[QueryStats]]:
        """Batch front end (DESIGN.md §5). Returns (ids, dists, stats).

        ``batch_mode="batched"`` (default) runs the cross-query amortized
        driver: one jit dispatch per phase for the whole batch and one
        tier-3 access per phase for the union of all queries' misses
        (DESIGN.md §5). ``batch_mode="loop"`` is the sequential
        one-query-at-a-time fallback kept for parity testing; both modes
        return identical (ids, dists). Whole-batch accounting (the
        amortized tier-3 access count) lands in ``self.last_batch_stats``;
        the per-query ``QueryStats.n_db`` records each query's *demand*
        (phases in which it missed), so summing it across a batch
        over-counts the shared fetches — by design.
        """
        cfg = self.config
        ef = ef or cfg.ef_search
        Q = np.asarray(Q, dtype=np.float32)
        B = len(Q)
        if self.n_live == 0:  # fully-tombstoned engine: nothing to return
            self.last_batch_stats = BatchStats(batch_size=B)
            return (np.full((B, k), -1, np.int32),
                    np.full((B, k), np.inf, np.float32),
                    [QueryStats() for _ in range(B)])
        filters, banned, ef = self._batch_filter(filt, B, ef)
        # mesh-sharded driver (DESIGN.md §10): takes precedence over the
        # fused single-device reroute — sharded search is itself fully
        # in-graph with device-resident per-shard payload
        if (cfg.n_shards > 1 and cfg.mode == "webanns"
                and batch_mode == "batched"):
            return self._sharded_many(Q, k, ef, banned)
        # fused engines run the whole query as one program (_query_fused);
        # the batched host driver would silently reroute them, so honor
        # cfg.fused via the sequential path until a fused batch exists
        if cfg.fused and cfg.mode == "webanns" and batch_mode == "batched":
            batch_mode = "loop"
        if batch_mode == "loop":
            out_i, out_d, out_s = zip(*(
                self._search_one(q, k, ef, boost=False,
                                 filt=None if filters is None else filters[b])
                for b, q in enumerate(Q)
            ))
            self.last_batch_stats = BatchStats(
                batch_size=B,
                n_db=sum(s.n_db for s in out_s),
                items_fetched=sum(s.items_fetched for s in out_s),
                t_in_mem=sum(s.t_in_mem for s in out_s),
                t_db=sum(s.t_db for s in out_s),
            )
            return np.stack(out_i), np.stack(out_d), list(out_s)
        if batch_mode != "batched":
            raise ValueError(
                f"batch_mode must be 'batched' or 'loop', got {batch_mode!r}"
            )
        ids, dists, per_stats, self.last_batch_stats = self._lazy_search(
            Q, k, ef, banned
        )
        return ids, dists, per_stats

    # ------------------------------------------------- typed session API

    def search(self, request: SearchRequest) -> SearchResult:
        """Serve one :class:`SearchRequest` — the canonical entry point.

        A ``(d,)`` query runs the single-query driver; a ``(B, d)``
        batch runs the driver selected by ``request.batch_mode`` and
        also carries the whole-batch accounting in
        ``SearchResult.batch_stats``.
        """
        with span("search"):
            return self._search(request)

    def _search(self, request: SearchRequest) -> SearchResult:
        q = np.asarray(request.query, dtype=np.float32)
        if q.ndim == 1:
            filt = request.filter
            if filt is not None and not isinstance(filt, Filter):
                raise ValueError(
                    "a single-query request takes a single Filter, not "
                    f"{type(filt).__name__}"
                )
            if self.config.n_shards > 1 and self.config.mode == "webanns":
                # sharded sessions serve single queries as a B=1 batch
                # through the mesh driver (DESIGN.md §10)
                ids, dists, stats = self._search_many(
                    q[None], request.k, request.ef, "batched", filt=filt,
                )
                return SearchResult(
                    ids=ids[0], dists=dists[0], stats=stats[0]
                )
            ids, dists, stats = self._search_one(
                q, request.k, request.ef, filt=filt
            )
            return SearchResult(ids=ids, dists=dists, stats=stats)
        if q.ndim != 2:
            raise ValueError(
                f"SearchRequest.query must be (d,) or (B, d), got {q.shape}"
            )
        ids, dists, stats = self._search_many(
            q, request.k, request.ef, request.batch_mode,
            filt=request.filter,
        )
        return SearchResult(
            ids=ids, dists=dists, stats=stats,
            batch_stats=self.last_batch_stats,
        )

    def get_texts(self, ids: np.ndarray) -> List[Optional[str]]:
        """Texts for ``ids``; ``None`` for unknown, padded (-1), AND
        tombstoned ids — deleted content must never resurface through a
        stale id (GDPR-style forgetting; RAGPipeline.remove_documents
        relies on this)."""
        if self.doc_store is None:
            return [None] * len(ids)
        return self.doc_store.get(ids, tombstones=self.tombstones)


class DocStore:
    """Id → text store, kept separate from embeddings (paper §4.1)."""

    def __init__(self, texts: List[Optional[str]]):
        self._texts = list(texts)

    def extend(self, texts: List[Optional[str]]) -> None:
        """Append texts for newly added ids (mutation lifecycle §8)."""
        self._texts.extend(texts)

    def get(self, ids, tombstones=None) -> List[Optional[str]]:
        """Texts by id; out-of-range ids come back None. ``tombstones``
        ((N,) bool) masks deleted ids to None — the raw rows are kept
        (ids are never reused) but must not be served."""
        out = []
        for i in np.asarray(ids).tolist():
            i = int(i)
            dead = (
                tombstones is not None
                and 0 <= i < len(tombstones)
                and bool(tombstones[i])
            )
            out.append(
                self._texts[i]
                if 0 <= i < len(self._texts) and not dead else None
            )
        return out
