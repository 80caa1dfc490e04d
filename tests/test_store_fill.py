"""The batched miss fill, ``TieredStore.fill_batch`` (DESIGN.md §5).

The batched driver hands the store ids that its phase programs found
absent from tier 2 (``stage_batch``), and its next phase program fills
them on the device without looking them up again. Two contracts:

1. **Parity** — a batched search through the driver's fill returns the
   ids and distances, and leaves the whole tier-2 ``CacheState``, EQUAL
   to the same search through the general ``gather_batch`` path, after
   every batch.
2. **Precondition** — every id the driver stages is absent from tier 2
   at call time (the fact the dropped lookup rests on).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.store import TieredStore, cache_lookup
from test_phase_loops import use_reference_loop

# (precision, eviction, mode, tier-2 capacity as a share of N)
CASES = {
    "f32-fifo": ("float32", "fifo", "webanns", 0.25),
    "f32-lru": ("float32", "lru", "webanns", 0.25),
    "int8-fifo": ("int8", "fifo", "webanns", 0.25),
    "int8-lru": ("int8", "lru", "webanns", 0.25),
    "f32-fifo-base": ("float32", "fifo", "webanns-base", 0.25),
    "int8-lru-base": ("int8", "lru", "webanns-base", 0.25),
    "f32-fifo-overflow": ("float32", "fifo", "webanns", 0.02),
    "f32-lru-overflow": ("float32", "lru", "webanns", 0.02),
    "int8-fifo-overflow": ("int8", "fifo", "webanns", 0.02),
}
OVERFLOW = {name for name in CASES if name.endswith("overflow")}


def _engine(X, g, case):
    precision, eviction, mode, share = CASES[case]
    return WebANNSEngine(X, g, EngineConfig(
        cache_capacity=max(8, int(len(X) * share)), eviction=eviction,
        mode=mode, precision=precision,
    ))


def _gather_batch_reference(engine):
    """Run the reference loop, its fill through the general path."""
    use_reference_loop(engine)
    store = engine.store
    store.fill_batch = lambda ids: jnp.asarray(store.gather_batch(ids))


def _assert_caches_equal(a, b):
    for name, x, y in zip(
        ("slab", "scales", "codebook", "slot_of", "id_of", "clock",
         "last_used"),
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def _unions(store):
    """Record each fill's union size, calling through to the staging."""
    sizes = []
    stage = store.stage_batch

    def recorded(ids):
        sizes.append(len(np.unique(ids[ids >= 0])))
        return stage(ids)

    store.stage_batch = recorded
    return sizes


@pytest.mark.parametrize("case", list(CASES))
def test_fill_batch_matches_gather_batch(small_dataset, small_graph, case):
    X, Q = small_dataset
    ref = _engine(X, small_graph, case)
    _gather_batch_reference(ref)
    eng = _engine(X, small_graph, case)
    unions = _unions(eng.store)
    for batch in (Q[:6], Q[6:], Q[3:9]):  # the third batch meets warm rows
        req = SearchRequest(query=batch, k=10, ef=32)
        want, got = ref.search(req), eng.search(req)
        np.testing.assert_array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(got.dists, want.dists)
        assert got.batch_stats.n_phases == want.batch_stats.n_phases
        _assert_caches_equal(eng.store.cache, ref.store.cache)
    assert eng.snapshot_access_stats()["n_db"] \
        == ref.snapshot_access_stats()["n_db"]
    assert eng.external.stats.items_used == ref.external.stats.items_used
    assert unions  # the fill engaged
    if case in OVERFLOW:  # the keep-newest overflow of cache_insert ran
        assert max(unions) > eng.store.capacity


@pytest.mark.parametrize("case", [c for c in CASES if c not in OVERFLOW])
def test_every_filled_id_is_absent_from_tier2(small_dataset, small_graph,
                                             case):
    X, Q = small_dataset
    eng = _engine(X, small_graph, case)
    store = eng.store
    stage = store.stage_batch
    checked = []

    def checked_stage(ids):
        union = np.unique(ids[ids >= 0])
        present, _ = cache_lookup(store.cache,
                                  jnp.asarray(TieredStore._pad_pow2(union)))
        assert not np.asarray(present).any()
        checked.append(len(union))
        return stage(ids)

    store.stage_batch = checked_stage
    for batch in (Q[:6], Q[6:]):
        eng.search(SearchRequest(query=batch, k=10, ef=32))
    assert len(checked) > 1


def test_fill_batch_rows_padding_and_one_access(small_dataset, small_graph):
    X, _ = small_dataset
    eng = _engine(X, small_graph, "f32-fifo")
    ids = np.array([[1, 2, 3, -1], [3, 2, 7, -1], [7, 1, -1, -1]], np.int32)
    out = eng.store.fill_batch(ids)
    assert isinstance(out, jax.Array) and out.shape == (3, 4, X.shape[1])
    out = np.asarray(out)
    valid = ids >= 0
    np.testing.assert_array_equal(out[valid], X[ids[valid]])
    assert (out[~valid] == 0).all()
    stats = eng.external.stats
    assert (stats.n_db, stats.items_fetched, stats.items_used) == (1, 4, 4)
    present, _ = cache_lookup(eng.store.cache, jnp.asarray([1, 2, 3, 7]))
    assert np.asarray(present).all()
    empty = eng.store.fill_batch(np.full((2, 3), -1, np.int32))
    assert (np.asarray(empty) == 0).all() and stats.n_db == 1
