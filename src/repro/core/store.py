"""Three-tier data management (paper §3.2), TPU-adapted.

Tier 1 (paper: Wasm heap / here: VMEM) is implicit — it is the BlockSpec
working set of the Pallas kernels and the registers of the fused search
loop; it has no persistent state.

Tier 2 (paper: JavaScript cache / here: per-device HBM cache slab) is
:class:`CacheState` — a fixed-capacity vector slab plus an id→slot map,
with pluggable eviction (FIFO default, as in the paper's prototype §4.1;
LRU and LFU-ish "clock" provided as beyond-paper options). All operations
are jittable pure functions on the pytree. The slab dtype is set by the
``precision`` knob (DESIGN.md §7): float32, float16, or int8 with a
per-row scale vector — inserts quantize, lookups dequantize, so the
search phases always see float32 while the resident footprint shrinks
by up to ~4× (the capacity the cache-size optimizer then re-spends).

Tier 3 (paper: IndexedDB / here: pluggable storage backend) is
:class:`ExternalStore` — an accounting shell (exact access counters +
the calibratable cost model ``t_access = t_setup + n_items * t_per_item``,
paper Fig. 3b) over a :class:`repro.core.storage.StorageBackend`:
in-memory numpy (the seed behavior), mmap-backed ``.npy`` vector shards
on disk, or any composition via :class:`repro.core.storage.LatencyModel`.
The counters make every experiment on n_db / redundancy / latency
decomposition (Eq. 1, Eq. 2) deterministic and reproducible.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq, quant
from repro.core.spans import span, to_host
from repro.core.storage import (  # noqa: F401  (re-exported, DESIGN.md §6)
    DeltaBackend,
    InMemoryBackend,
    LatencyModel,
    ShardedFileBackend,
    StorageBackend,
    unwrap_backend,
)

EVICT_FIFO = 0
EVICT_LRU = 1

_EVICTION_NAMES = {"fifo": EVICT_FIFO, "lru": EVICT_LRU}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CacheState:
    """Tier-2 cache: fixed-capacity slab + id→slot map (jittable pytree).

    ``slab`` holds vectors at the cache's precision (float32 / float16 /
    int8 / pq); ``scales`` carries the per-row dequantization scale —
    only int8 slabs need one, so the other precisions carry a (0,) leaf
    and pay neither the 4 bytes/row nor the insert-time scatter. At
    ``"pq"`` the slab is (capacity, M) uint8 PQ codes — M bytes per row,
    the DRAM-free mode (DESIGN.md §12) — and ``codebook`` carries the
    frozen (M, 256, dsub) centroids inserts encode through and lookups
    decode through; the other precisions carry a (0, 0, 0) leaf so the
    pytree structure is uniform. The slab dtype is part of every jitted
    op's trace signature, so each precision compiles its own (cheap)
    specialization and the float32 path is byte-identical to the
    pre-quantization cache.
    """

    slab: jnp.ndarray  # (capacity, d) f32/f16/int8 — or (capacity, M) u8
    scales: jnp.ndarray  # (capacity,) f32 dequant scales; (0,) if not int8
    codebook: jnp.ndarray  # (M, 256, dsub) f32 PQ centroids; (0,0,0) else
    slot_of: jnp.ndarray  # (N,) int32 — slot of id, -1 if absent
    id_of: jnp.ndarray  # (capacity,) int32 — id in slot, -1 if empty
    clock: jnp.ndarray  # () int32 — insertion cursor (FIFO) / tick (LRU)
    last_used: jnp.ndarray  # (capacity,) int32 — LRU timestamps

    @property
    def capacity(self) -> int:
        return int(self.slab.shape[0])

    @property
    def precision(self) -> str:
        return {
            jnp.dtype(jnp.float32): "float32",
            jnp.dtype(jnp.float16): "float16",
            jnp.dtype(jnp.int8): "int8",
            jnp.dtype(jnp.uint8): "pq",
        }[jnp.dtype(self.slab.dtype)]

    def nbytes(self) -> int:
        """Resident tier-2 payload bytes (slab + scales when quantized).
        For pq slabs the row width IS the subspace count, so the shared
        codebook is not charged per row (it amortizes across the corpus
        — same accounting as ``quant.bytes_per_vector``)."""
        cap, dim = self.slab.shape
        if self.precision == "pq":
            return cap * int(dim)  # dim == n_subspaces for a code slab
        return cap * quant.bytes_per_vector(int(dim), self.precision)


def cache_init(
    n_items: int,
    capacity: int,
    dim: int,
    precision: str = "float32",
    codebook: Optional[np.ndarray] = None,
) -> CacheState:
    capacity = int(max(1, capacity))
    precision = quant.canonical_precision(precision)
    n_scales = capacity if precision == "int8" else 0
    if precision == "pq":
        if codebook is None:
            raise ValueError(
                "a pq cache needs its trained codebook — pass the "
                "(M, 256, dsub) centroids (see repro.core.pq.train_pq)"
            )
        cent = jnp.asarray(
            getattr(codebook, "centroids", codebook), jnp.float32
        )
        if cent.shape[0] * cent.shape[2] != int(dim):
            raise ValueError(
                f"codebook covers dim {cent.shape[0] * cent.shape[2]}, "
                f"cache holds dim {dim}"
            )
        row_width = cent.shape[0]  # M code bytes per cached row
    else:
        cent = jnp.zeros((0, 0, 0), jnp.float32)
        row_width = dim
    return CacheState(
        slab=jnp.zeros((capacity, row_width), quant.slab_dtype(precision)),
        scales=jnp.ones((n_scales,), jnp.float32),
        codebook=cent,
        slot_of=jnp.full((n_items,), -1, jnp.int32),
        id_of=jnp.full((capacity,), -1, jnp.int32),
        clock=jnp.zeros((), jnp.int32),
        last_used=jnp.zeros((capacity,), jnp.int32),
    )


def cache_lookup(
    cache: CacheState, ids: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized membership + gather. ids may contain -1 padding.

    Returns (present (k,) bool, vectors (k, d) — garbage rows where
    absent). Vectors come back float32 regardless of the slab precision:
    int8 rows are dequantized against their per-row scale on the way out
    (the jnp twin of the fused dequant–gather kernels in
    ``kernels/dequant_gather_distance.py``).
    """
    safe_ids = jnp.clip(ids, 0, cache.slot_of.shape[0] - 1)
    slots = cache.slot_of[safe_ids]
    safe_slots = jnp.clip(slots, 0, cache.capacity - 1)
    # id_of cross-check guards against stale mappings after ring wrap
    present = (slots >= 0) & (ids >= 0) & (cache.id_of[safe_slots] == ids)
    vecs = cache.slab[safe_slots]
    if vecs.dtype == jnp.int8:
        vecs = vecs.astype(jnp.float32) * cache.scales[safe_slots][..., None]
    elif vecs.dtype == jnp.uint8:
        # pq slab: decode codes through the frozen codebook. By the
        # subspace decomposition (DESIGN.md §12) the distances the
        # drivers then compute on the decoded rows ARE the ADC distances
        # — this is the jnp twin of kernels/adc_gather_distance.py.
        vecs = pq.decode_jnp(vecs, cache.codebook)
    elif vecs.dtype != jnp.float32:
        vecs = vecs.astype(jnp.float32)
    return present, vecs


def cache_lookup_batch(
    cache: CacheState, ids: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched membership + gather for a (B, k) id matrix (-1 padded).

    Returns (present (B, k) bool, vectors (B, k, d)). All ops in
    :func:`cache_lookup` are elementwise gathers, so the 2-D form is the
    same computation — this wrapper exists so the batched driver's
    contract (DESIGN.md §5) is an explicit, tested API.
    """
    return cache_lookup(cache, ids)


def cache_insert_batch(
    cache: CacheState,
    ids: jnp.ndarray,  # (B, k) int32, -1 padded
    vecs: jnp.ndarray,  # (B, k, d) float32
    policy: int = EVICT_FIFO,
) -> CacheState:
    """Insert a (B, k) fetched batch by flattening to one (B*k,) insert.

    Duplicate ids across rows cost a wasted slot each (one slot_of write
    wins arbitrarily; the id_of cross-check in lookup keeps the winner
    consistent) — the batched driver avoids this by deduplicating the
    miss union host-side before fetching (DESIGN.md §5), so flatten-insert
    here only ever sees unique ids on the hot path.
    """
    B, k = ids.shape
    return cache_insert(
        cache, ids.reshape(B * k), vecs.reshape(B * k, -1), policy=policy
    )


@jax.jit
def cache_evict(cache: CacheState, ids: jnp.ndarray) -> CacheState:
    """Drop ``ids`` from tier 2 (delete/upsert invalidation). Jittable.

    Clears both directions of the id↔slot map so ``cache_lookup`` can
    never serve a tombstoned row again; freed slots get a zeroed LRU
    stamp (stalest possible → reclaimed first). The slab row itself is
    left as garbage — unreachable once unmapped, same contract as a
    ring-wrap eviction. Absent / -1 ids are no-ops.
    """
    n = cache.slot_of.shape[0]
    cap = cache.capacity
    safe_ids = jnp.clip(ids, 0, n - 1)
    slots = cache.slot_of[safe_ids]
    safe_slots = jnp.clip(slots, 0, cap - 1)
    # only clear slots whose mapping is current (id_of cross-check),
    # mirroring cache_lookup's staleness guard
    ok = (ids >= 0) & (slots >= 0) & (cache.id_of[safe_slots] == ids)
    id_of = cache.id_of.at[jnp.where(ok, slots, cap)].set(-1, mode="drop")
    last_used = cache.last_used.at[jnp.where(ok, slots, cap)].set(
        0, mode="drop"
    )
    slot_of = cache.slot_of.at[jnp.where(ids >= 0, ids, n)].set(
        -1, mode="drop"
    )
    return dataclasses.replace(
        cache, slot_of=slot_of, id_of=id_of, last_used=last_used
    )


def cache_grow(cache: CacheState, n_items: int) -> CacheState:
    """Extend the id space of ``slot_of`` to ``n_items`` (new ids start
    absent). Capacity/slab are untouched — adding corpus rows does not
    resize tier 2. The (N,) shape is part of the jit trace signature,
    so the first query after a grow re-traces (documented §8)."""
    extra = int(n_items) - cache.slot_of.shape[0]
    if extra < 0:
        raise ValueError("cache id space cannot shrink")
    if extra == 0:
        return cache
    slot_of = jnp.concatenate(
        [cache.slot_of, jnp.full((extra,), -1, jnp.int32)]
    )
    return dataclasses.replace(cache, slot_of=slot_of)


def cache_touch(cache: CacheState, ids: jnp.ndarray) -> CacheState:
    """LRU bookkeeping for a batch of accessed ids (no-op rows for -1)."""
    safe_ids = jnp.clip(ids, 0, cache.slot_of.shape[0] - 1)
    slots = cache.slot_of[safe_ids]
    ok = (slots >= 0) & (ids >= 0)
    tick = cache.clock + 1
    last = cache.last_used.at[jnp.where(ok, slots, 0)].max(
        jnp.where(ok, tick, 0)
    )
    return dataclasses.replace(cache, last_used=last, clock=tick)


@functools.partial(jax.jit, static_argnames=("policy",))
def cache_insert(
    cache: CacheState,
    ids: jnp.ndarray,  # (k,) int32, -1 padded
    vecs: jnp.ndarray,  # (k, d) float32
    policy: int = EVICT_FIFO,
) -> CacheState:
    """Insert a fetched batch, evicting per ``policy``. Jittable.

    ``vecs`` arrive float32 (tier-3 fetches are always full precision);
    they are quantized to the slab's precision on the way in, with the
    per-row scale written alongside. FIFO: slots are a ring buffer
    advanced by the insert cursor (paper's prototype behavior). LRU:
    each insert claims the least-recently-used slot (computed per batch
    via top_k on stale timestamps).

    Overflow contract (defined, tested): when one insert batch exceeds
    capacity, both policies recycle slots, so several rows of the batch
    target the same slot. All but the LAST such row are dropped
    ("keep-newest"): the cache ends up holding exactly the final
    ``capacity`` inserted ids, never a scatter-order-dependent mix.
    Ids are assumed unique within a batch (callers dedup; duplicate ids
    may still waste a slot each, as documented in cache_insert_batch).
    """
    k = ids.shape[0]
    cap = cache.capacity
    valid = ids >= 0
    already_present, _ = cache_lookup(cache, ids)
    need = valid & ~already_present

    if policy == EVICT_FIFO:
        offsets = jnp.cumsum(need.astype(jnp.int32)) - 1
        slots = (cache.clock + jnp.where(need, offsets, 0)) % cap
        new_clock = cache.clock + jnp.sum(need.astype(jnp.int32))
    else:  # LRU: pick the stalest slots, recycled cyclically if k > cap
        m = min(k, cap)
        stale = -cache.last_used
        _, lru_slots = jax.lax.top_k(stale, m)
        offsets = jnp.cumsum(need.astype(jnp.int32)) - 1
        slots = lru_slots[jnp.clip(offsets, 0, k - 1) % m]
        new_clock = cache.clock + 1

    slots = jnp.where(need, slots, cap)  # out-of-range = dropped scatter
    # keep-newest dedup: scatter with duplicate indices has no defined
    # ordering, so drop every row except the last one targeting each slot
    order = jnp.arange(k, dtype=jnp.int32)
    winner = jnp.full((cap,), -1, jnp.int32).at[slots].max(
        jnp.where(need, order, -1), mode="drop"
    )
    need = need & (winner[jnp.clip(slots, 0, cap - 1)] == order)
    slots = jnp.where(need, slots, cap)
    n_items = cache.slot_of.shape[0]
    # 1) unmap evicted ids (inactive rows scatter out-of-range → dropped;
    # never to a real index, which would clobber it under duplicate-index
    # scatter with undefined ordering)
    evicted = cache.id_of[jnp.clip(slots, 0, cap - 1)]
    evict_ok = need & (evicted >= 0)
    e_idx = jnp.where(evict_ok, evicted, n_items)
    slot_of = cache.slot_of.at[e_idx].set(-1, mode="drop")
    # 2) write new vectors / maps (mode='drop' ignores out-of-range rows)
    i_idx = jnp.where(need, ids, n_items)
    slot_of = slot_of.at[i_idx].set(slots, mode="drop")
    scales = cache.scales  # float slabs: (0,) leaf, nothing to write
    if cache.precision == "pq":
        # encode through the frozen codebook (re-encoding a decoded row
        # is stable, so refetch-after-eviction never drifts — §12)
        payload = pq.encode_jnp(vecs, cache.codebook)
    else:
        payload, row_scales = quant.quantize_jnp(vecs, cache.precision)
        if cache.precision == "int8":
            scales = scales.at[slots].set(row_scales, mode="drop")
    slab = cache.slab.at[slots, :].set(payload, mode="drop")
    id_of = cache.id_of.at[slots].set(ids, mode="drop")
    last_used = cache.last_used.at[slots].set(new_clock, mode="drop")
    return CacheState(
        slab=slab,
        scales=scales,
        codebook=cache.codebook,
        slot_of=slot_of,
        id_of=id_of,
        clock=new_clock,
        last_used=last_used,
    )


@functools.partial(jax.jit, static_argnames=("shape", "policy"))
def fill_union(
    cache: CacheState,
    idx: jnp.ndarray,  # (u + prod(shape),) int32: the union, then ``inv``
    rows: jnp.ndarray,  # (u, d) float32 tier-3 rows, zero padded
    shape: Tuple[int, ...],  # of the miss lists
    policy: int,
) -> Tuple[CacheState, jnp.ndarray]:
    """Insert a union of known misses, touch it under LRU, and scatter
    its rows back to the miss lists: the device half of
    :meth:`TieredStore.fill` and :meth:`TieredStore.fill_batch`, whose
    staging packs ``idx`` as the -1-padded union (``u`` ids, as many as
    ``rows``) followed by ``inv``, each miss's row in the union (-1
    padded), flattened. The lazy drivers call it inside their phase
    programs. Returns the cache and the ``shape + (d,)`` rows."""
    u = rows.shape[0]
    ids, inv = idx[:u], idx[u:].reshape(shape)
    cache = cache_insert(cache, ids, rows, policy=policy)
    if policy == EVICT_LRU:
        cache = cache_touch(cache, ids)
    out = jnp.where((inv >= 0)[..., None], rows[jnp.maximum(inv, 0)], 0.0)
    return cache, out


# --------------------------------------------------------------- tier 3


@dataclasses.dataclass
class AccessStats:
    """Counters behind Eq. 1 (redundancy) and Eq. 2 (latency model),
    and the host phase loop's tier-2 and sync counters.

    The three last counters move only in the host-driven lazy drivers
    (single-query and batched); the fused and sharded drivers run their
    phases on the device and leave them unchanged. ``tier2_misses``
    counts the ids the phase programs pushed to the lazy list;
    ``tier2_hits`` the neighbour lookups tier 2 served inside beam
    phases (distance evaluations less misses; the hits of a layer's
    entry probe are not counted). ``host_syncs`` counts the blocking
    device->host reads of those drivers (one miss-list read per phase
    program) and of ``TieredStore.gather``, which no driver calls
    (the fills and their staging make none).
    """

    n_db: int = 0  # number of external accesses (transactions)
    items_fetched: int = 0  # total items pulled from tier 3
    items_used: int = 0  # items that were actually needed (#hit in Eq. 1)
    modeled_time: float = 0.0  # sum of modeled t_db per access
    wall_time: float = 0.0  # measured host time in fetch calls
    tier2_hits: int = 0  # beam-phase lookups served by tier 2
    tier2_misses: int = 0  # ids pushed to the lazy list
    host_syncs: int = 0  # blocking device->host reads

    def redundancy(self) -> float:
        """Eq. 1: R = 1 - hits / (n_db * prefetch_size)."""
        if self.items_fetched == 0:
            return 0.0
        return 1.0 - self.items_used / self.items_fetched

    def reset(self) -> None:
        self.n_db = 0
        self.items_fetched = 0
        self.items_used = 0
        self.modeled_time = 0.0
        self.wall_time = 0.0
        self.tier2_hits = 0
        self.tier2_misses = 0
        self.host_syncs = 0


class ExternalStore:
    """Tier 3: accounting shell (counters + cost model) over a backend.

    ``source`` may be a raw ``(N, d)`` array (wrapped in
    :class:`InMemoryBackend` — the seed behavior) or any
    :class:`StorageBackend`. Unless the given backend already carries a
    :class:`LatencyModel`, one is composed from ``t_setup`` /
    ``t_per_item`` / ``simulate_latency``; ``t_setup`` dominates per
    paper Fig. 3b ("all-in-one loading is ~45% faster than sequential")
    and the default constants reproduce that ratio. With
    ``simulate_latency=True`` fetches actually sleep (end-to-end
    wall-clock realism); by default latency is accounted analytically so
    tests stay fast and deterministic.
    """

    def __init__(
        self,
        source: Union[np.ndarray, StorageBackend],
        t_setup: float = 1.0e-3,
        t_per_item: float = 2.0e-6,
        simulate_latency: bool = False,
    ):
        if not hasattr(source, "fetch"):  # raw array (or array-like)
            backend: StorageBackend = InMemoryBackend(source)
        else:
            backend = source
        if not isinstance(backend, LatencyModel):
            backend = LatencyModel(
                backend, t_setup, t_per_item, simulate_latency
            )
        self.backend: StorageBackend = backend
        self.stats = AccessStats()
        self._pending: set = set()  # fetched ids not yet demanded

    @property
    def base_backend(self) -> StorageBackend:
        """The storage medium itself, LatencyModel wrappers stripped."""
        return unwrap_backend(self.backend)

    def append(self, rows: np.ndarray) -> np.ndarray:
        """Append payload rows for the mutation lifecycle (DESIGN.md §8).

        On first use the storage medium is wrapped in a
        :class:`DeltaBackend` *inside* any LatencyModel chain, so the
        cost model keeps covering every fetch while the medium itself
        stays frozen. Appends are init-stage work (not a query-time
        access), so no counters move. Returns the new rows' ids.
        """
        base = self.base_backend
        if not isinstance(base, DeltaBackend):
            delta = DeltaBackend(base)
            b = self.backend
            if isinstance(b, LatencyModel):
                while isinstance(b.inner, LatencyModel):
                    b = b.inner
                b.inner = delta
            else:
                self.backend = delta
            base = delta
        return base.append(rows)

    @property
    def vectors(self) -> np.ndarray:
        """Full payload, materialized (init-stage all-in-one load)."""
        return self.backend.vectors

    @property
    def t_setup(self) -> float:
        b = self.backend
        return b.t_setup if isinstance(b, LatencyModel) else 0.0

    @property
    def t_per_item(self) -> float:
        b = self.backend
        return b.t_per_item if isinstance(b, LatencyModel) else 0.0

    @property
    def simulate_latency(self) -> bool:
        b = self.backend
        return b.simulate if isinstance(b, LatencyModel) else False

    @property
    def n_items(self) -> int:
        return self.backend.n_items

    @property
    def dim(self) -> int:
        return self.backend.dim

    def access_cost(self, n: int) -> float:
        return self.backend.access_cost(n)

    def fetch(self, ids: np.ndarray) -> np.ndarray:
        """ONE external access (one 'transaction') for a batch of ids."""
        with span("tier3_fetch"):
            t0 = time.perf_counter()
            ids = np.asarray(ids)
            ids = ids[ids >= 0]
            out = self.backend.fetch(ids)
            cost = self.access_cost(len(ids))
            self.stats.n_db += 1
            self.stats.items_fetched += len(ids)
            self.stats.modeled_time += cost
            self.stats.wall_time += time.perf_counter() - t0
            self._pending.update(int(i) for i in ids)
            return out

    def fetch_sequential(self, ids: np.ndarray) -> np.ndarray:
        """n separate accesses for n items (paper Fig. 3b's slow path)."""
        ids = np.asarray(ids)
        ids = ids[ids >= 0]
        out = np.empty((len(ids), self.dim), np.float32)
        for j, i in enumerate(ids):
            out[j] = self.fetch(np.array([i]))
        return out

    def mark_used(self, n: int) -> None:
        self.stats.items_used += int(n)

    def mark_used_ids(self, ids) -> None:
        """Eq. 1 hit accounting, per fetch event: each fetched copy of an
        item counts as 'used' when first demanded after that fetch.
        Repeat hits don't double-count; a refetch-after-eviction that is
        demanded again is useful work, not redundancy."""
        for i in np.atleast_1d(np.asarray(ids)).tolist():
            i = int(i)
            if i in self._pending:
                self._pending.discard(i)
                self.stats.items_used += 1


class TieredStore:
    """Tier 2 + tier 3 composition used by the engine driver.

    ``fill(ids)`` and ``fill_batch(ids)``: fetch ids known to miss tier
    2 from tier 3 in ONE access, insert them, and return their rows on
    the device. This is the bulk phase-2 load of the lazy search
    (Algorithm 1 line 24), of one miss list or of B. Each is a host
    half (``stage`` / ``stage_batch``: union, fetch, padding) and a
    device half (:func:`fill_union`); the lazy drivers call the halves,
    the second inside their phase programs. ``gather(ids)`` is the
    general form for ids that may hit: it looks tier 2 up, fetches only
    the misses, and returns all rows on the host.
    """

    def __init__(
        self,
        external: ExternalStore,
        capacity: int,
        eviction: str = "fifo",
        precision: str = "float32",
        codebook=None,  # PQCodebook / (M, 256, dsub) centroids; pq only
    ):
        self.external = external
        self.eviction = _EVICTION_NAMES[eviction]
        self.precision = quant.canonical_precision(precision)
        self.codebook = codebook
        self.cache = cache_init(
            external.n_items, capacity, external.dim, self.precision,
            codebook=codebook,
        )

    @property
    def capacity(self) -> int:
        return self.cache.capacity

    def cache_bytes(self) -> int:
        """Resident tier-2 payload bytes at the current precision."""
        return self.cache.nbytes()

    def resize(self, capacity: int) -> None:
        """Re-initialize tier 2 with a new capacity (cache-size optimizer).
        The codebook survives the resize — it is frozen corpus state,
        not cache contents."""
        self.cache = cache_init(
            self.external.n_items, capacity, self.external.dim,
            self.precision, codebook=self.codebook,
        )

    def lookup(self, ids: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return cache_lookup(self.cache, ids)

    def invalidate(self, ids: np.ndarray) -> None:
        """Evict ``ids`` from tier 2 (delete/upsert invalidation)."""
        ids = np.asarray(ids, dtype=np.int32)
        self.cache = cache_evict(
            self.cache, jnp.asarray(self._pad_pow2(ids))
        )

    def grow(self, n_items: int) -> None:
        """Extend the cache's id space after corpus rows were appended."""
        self.cache = cache_grow(self.cache, n_items)

    # floor of the padded-shape buckets: with a bare next-pow2 bucket
    # every novel small miss-union size (1, 2, 3→4, 5→8, …) compiled its
    # own cache-op specialization, and those one-off compiles landed in
    # measured query time (the bs=16 p99 outlier in BENCH_query.json).
    # Flooring at 64 collapses the bucket set to {64, 128, 256, …} — a
    # handful of shapes that the bench warmup can exhaustively pre-trace.
    PAD_FLOOR = 64

    @staticmethod
    def _bucket(n: int) -> int:
        """The power-of-2 bucket (floored at :data:`PAD_FLOOR`) of ``n``."""
        return max(TieredStore.PAD_FLOOR, 1 << (max(1, n) - 1).bit_length())

    @staticmethod
    def _pad_pow2(ids: np.ndarray) -> np.ndarray:
        """Pad id batches to a SMALL fixed set of power-of-2 buckets
        (floored at :data:`PAD_FLOOR`) so the jitted cache ops trace once
        per bucket instead of once per novel batch size."""
        out = np.full(TieredStore._bucket(len(ids)), -1, np.int32)
        out[: len(ids)] = ids
        return out

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Bulk gather with single-access miss fill. ids: (k,) no padding.

        No driver calls it: the lazy drivers hand the store known misses
        (:meth:`stage`, :meth:`stage_batch`). :meth:`gather_batch` and
        the tests, which hold the fills to it, do."""
        ids = np.asarray(ids, dtype=np.int32)
        k = len(ids)
        padded = self._pad_pow2(ids)
        present, vecs = cache_lookup(self.cache, jnp.asarray(padded))
        stats = self.external.stats
        present = to_host(present, stats)[:k]
        vecs = np.array(to_host(vecs, stats)[:k])  # writable host copy
        if not present.all():
            miss_ids = ids[~present]
            fetched = self.external.fetch(miss_ids)
            miss_padded = self._pad_pow2(miss_ids)
            fetch_padded = np.zeros(
                (len(miss_padded), self.external.dim), np.float32
            )
            fetch_padded[: len(miss_ids)] = fetched
            self.cache = cache_insert(
                self.cache,
                jnp.asarray(miss_padded),
                jnp.asarray(fetch_padded),
                policy=self.eviction,
            )
            vecs[~present] = fetched
        self.external.mark_used_ids(ids)  # every gathered id is demanded
        if self.eviction == EVICT_LRU:
            self.cache = cache_touch(self.cache, jnp.asarray(padded))
        return vecs

    def gather_batch(self, ids: np.ndarray) -> np.ndarray:
        """Cross-query amortized bulk gather (DESIGN.md §5).

        ``ids`` is a (B, k) matrix of -1-padded per-query miss lists. The
        rows are unioned and deduplicated host-side, the union's tier-2
        misses are fetched from tier 3 in ONE access via :meth:`gather`
        (so an id missed by many queries is fetched exactly once), and
        the result is scattered back to per-row (B, k, d) vectors.
        Padded (-1) rows come back zero.
        """
        ids = np.asarray(ids, dtype=np.int32)
        B, k = ids.shape
        out = np.zeros((B, k, self.external.dim), np.float32)
        valid = ids >= 0
        if not valid.any():
            return out
        union = np.unique(ids[valid])  # sorted — searchsorted below
        union_vecs = self.gather(union)
        out[valid] = union_vecs[np.searchsorted(union, ids[valid])]
        return out

    def fill_batch(self, ids: np.ndarray) -> jnp.ndarray:
        """Batched miss fill for ids known to be absent (DESIGN.md §5).

        ``ids`` is a (B, k) matrix of -1-padded per-query miss lists, and
        every valid id in it must be absent from tier 2 at call time. So
        no lookup is made: :meth:`stage_batch` fetches the union from
        tier 3 in ONE access, and :func:`fill_union`, one program,
        inserts and (under LRU) touches it and scatters it back to
        per-row vectors. Returns those (B, k, d) float32 rows on the
        device (padded rows zero): the values, ids, slots and clocks
        that :meth:`gather_batch` gives on the same misses, with no
        device->host read. The batched driver runs the same two halves,
        with the device half inside its phase program, not this method.
        """
        ids = np.asarray(ids, dtype=np.int32)
        if not (ids >= 0).any():
            return jnp.zeros((*ids.shape, self.external.dim), jnp.float32)
        return self._fill(self.stage_batch(ids), ids.shape)

    def fill(self, ids: np.ndarray) -> jnp.ndarray:
        """Single-query miss fill for ids known to be absent (DESIGN.md §5).

        ``ids`` is the single-query driver's whole -1-padded miss list,
        of its fixed length ``miss_cap``; every valid id in it was found
        missing by the phase program that listed it, and the ids are
        distinct. :meth:`stage` fetches them from tier 3 in ONE access,
        and :func:`fill_union` inserts them in list order, so slots and
        clocks are those :meth:`gather` gives on the same list. Returns
        the (miss_cap, d) float32 rows on the device in list order
        (padded rows zero), with no device->host read. The single-query
        driver runs the same two halves, with the device half inside
        its phase program, not this method.
        """
        ids = np.asarray(ids, dtype=np.int32)
        if not (ids >= 0).any():
            return jnp.zeros((len(ids), self.external.dim), jnp.float32)
        return self._fill(self.stage(ids), ids.shape)

    def _fill(self, staged: Tuple[np.ndarray, np.ndarray],
              shape: Tuple[int, ...]) -> jnp.ndarray:
        self.cache, out = fill_union(self.cache, *staged, shape=shape,
                                     policy=self.eviction)
        return out

    def stage_batch(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The host half of :meth:`fill_batch`: the sorted union of the
        (B, k) miss lists, each miss's row in it, and ONE tier-3 access
        for it, as :func:`fill_union`'s ``(idx, rows)``. The union is
        padded to its power-of-two bucket, which is never wider than
        the bucket of B × k."""
        ids = np.asarray(ids, dtype=np.int32)
        valid = ids >= 0
        union = np.unique(ids[valid])  # sorted — searchsorted below
        inv = np.full(ids.shape, -1, np.int32)
        inv[valid] = np.searchsorted(union, ids[valid])
        return self._stage(union, self._bucket(len(union)), inv)

    def stage(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The host half of :meth:`fill`: the list's misses in list
        order, padded to the list's length, so the programs that take
        them are fixed by the layer's shapes, whatever the miss count;
        and ONE tier-3 access for them."""
        ids = np.asarray(ids, dtype=np.int32)
        valid = ids >= 0
        inv = np.full(ids.shape, -1, np.int32)
        inv[valid] = np.arange(int(valid.sum()), dtype=np.int32)
        return self._stage(ids[valid], len(ids), inv)

    def _stage(self, union: np.ndarray, width: int,
               inv: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch ``union`` in one access and pack it, padded to ``width``
        rows, with ``inv`` into one int32 array: two host->device copies
        a fill, not three."""
        idx = np.full(width + inv.size, -1, np.int32)
        idx[: len(union)] = union
        idx[width:] = inv.ravel()
        rows = np.zeros((width, self.external.dim), np.float32)
        rows[: len(union)] = self.external.fetch(union)
        self.external.mark_used_ids(union)  # every gathered id is demanded
        return idx, rows

    def warm(self, ids: np.ndarray) -> None:
        """Pre-populate tier 2 (initialization-stage index loading).

        Reads through the backend protocol (works for any medium, not
        just in-memory arrays) but bypasses the AccessStats counters AND
        the LatencyModel wrappers: init-stage loading is not a
        query-time access in Eq. 1/Eq. 2, so it is neither counted nor
        simulated.
        """
        ids = np.asarray(ids, dtype=np.int32)
        padded = self._pad_pow2(ids)
        vecs = np.zeros((len(padded), self.external.dim), np.float32)
        vecs[: len(ids)] = self.external.base_backend.fetch(ids)
        self.cache = cache_insert(
            self.cache, jnp.asarray(padded), jnp.asarray(vecs),
            policy=self.eviction,
        )
