"""The single-query miss fill, ``TieredStore.fill`` (DESIGN.md §5).

The single-query lazy driver reads its whole fixed-length miss list and
hands it to the store (``stage``), and its next phase program fills the
misses on the device with the batched fill's device half. Three
contracts:

1. **Parity** — single-query searches through the driver's fill return
   the ids and distances, and leave the whole tier-2 ``CacheState``,
   EQUAL to the same searches through the general ``gather`` path,
   after every query.
2. **No program in the window** — once warmed, new queries build no
   program: the fill's shapes are fixed by the layer's ``miss_cap``,
   whatever the miss count.
3. **Quality** — the ``cos`` path keeps HNSW-grade recall.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.eval import brute_force_topk, recall_at_k
from repro.core.hnsw import build_hnsw
from repro.core.metadata import Filter
from test_phase_loops import use_reference_loop

# (metric, eviction, precision, mode, tier-2 capacity as a share of N,
# filtered)
CASES = {
    f"{metric}-{eviction}-{precision}-{mode}":
        (metric, eviction, precision, mode, 0.25, False)
    for metric in ("l2", "cos")
    for eviction in ("fifo", "lru")
    for precision in ("float32", "int8")
    for mode in ("webanns", "webanns-base")
}
CASES["l2-fifo-float32-webanns-filtered"] = (
    "l2", "fifo", "float32", "webanns", 0.25, True)
CASES["cos-lru-float32-webanns-overflow"] = (
    "cos", "lru", "float32", "webanns", 0.02, False)
OVERFLOW = {name for name in CASES if name.endswith("overflow")}


@pytest.fixture(scope="module")
def graphs(small_dataset):
    X, _ = small_dataset
    return {metric: build_hnsw(X, M=8, ef_construction=60, metric=metric,
                               seed=3)
            for metric in ("l2", "cos")}


def _engine(X, graphs, case):
    metric, eviction, precision, mode, share, filtered = CASES[case]
    meta = {"user": np.arange(len(X)) % 3} if filtered else None
    return WebANNSEngine(X, graphs[metric], EngineConfig(
        cache_capacity=max(8, int(len(X) * share)), eviction=eviction,
        mode=mode, precision=precision, metric=metric,
    ), metadata=meta)


def _gather_reference(engine):
    """Run the reference loop, its fill through the general path."""
    use_reference_loop(engine)
    store = engine.store

    def fill(ids):
        miss = ids[ids >= 0]
        out = np.zeros((len(ids), store.external.dim), np.float32)
        out[: len(miss)] = store.gather(miss)
        return jnp.asarray(out)

    store.fill = fill


def _record(store):
    """Record each fill's list length and miss count, calling through to
    the staging."""
    calls = []
    stage = store.stage

    def recorded(ids):
        calls.append((len(ids), int((ids >= 0).sum())))
        return stage(ids)

    store.stage = recorded
    return calls


def _assert_caches_equal(a, b):
    for name, x, y in zip(
        ("slab", "scales", "codebook", "slot_of", "id_of", "clock",
         "last_used"),
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


@pytest.mark.parametrize("case", list(CASES))
def test_fill_matches_gather(small_dataset, graphs, case):
    X, Q = small_dataset
    filt = Filter.eq("user", 1) if CASES[case][5] else None
    ref = _engine(X, graphs, case)
    _gather_reference(ref)
    eng = _engine(X, graphs, case)
    calls = _record(eng.store)
    for q in (Q[0], Q[1], Q[0], Q[2]):  # the third query meets warm rows
        req = SearchRequest(query=q, k=10, ef=32, filter=filt)
        want, got = ref.search(req), eng.search(req)
        np.testing.assert_array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(got.dists, want.dists)
        assert (got.stats.n_db, got.stats.items_fetched) \
            == (want.stats.n_db, want.stats.items_fetched)
        _assert_caches_equal(eng.store.cache, ref.store.cache)
    for key in ("n_db", "items_fetched", "tier2_hits", "tier2_misses"):
        assert eng.snapshot_access_stats()[key] \
            == ref.snapshot_access_stats()[key], key
    assert eng.external.stats.items_used == ref.external.stats.items_used
    assert calls  # the fill engaged
    if filt is not None:
        assert all(np.isin(eng.search(SearchRequest(
            query=Q[3], k=10, ef=32, filter=filt)).ids % 3, [1]))
    if case in OVERFLOW:  # one phase's misses overflow tier 2
        assert max(n for _, n in calls) > eng.store.capacity


def test_fill_rows_in_list_order_and_one_access(small_dataset, graphs):
    X, _ = small_dataset
    eng = _engine(X, graphs, "l2-fifo-float32-webanns")
    ids = np.array([7, 3, 11, -1, -1], np.int32)
    out = eng.store.fill(ids)
    assert isinstance(out, jax.Array) and out.shape == (5, X.shape[1])
    out = np.asarray(out)
    np.testing.assert_array_equal(out[:3], X[[7, 3, 11]])
    assert (out[3:] == 0).all()
    stats = eng.external.stats
    assert (stats.n_db, stats.items_fetched, stats.items_used) == (1, 3, 3)
    # slots follow the list's order, as gather's insert does
    np.testing.assert_array_equal(np.asarray(eng.store.cache.id_of)[:3],
                                  [7, 3, 11])
    empty = eng.store.fill(np.full(5, -1, np.int32))
    assert empty.shape == (5, X.shape[1]) and stats.n_db == 1


class _Builds:
    """Programs this process builds or loads from the persistent
    compilation cache, from JAX's monitoring events."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n = 0

    def on_build(self, name, _secs, **_kw):
        self.n += name == self.BUILD

    def on_load(self, name, **_kw):
        self.n += name == self.LOAD


@pytest.fixture
def builds():
    counter = _Builds()
    jax.monitoring.register_event_duration_secs_listener(counter.on_build)
    jax.monitoring.register_event_listener(counter.on_load)
    yield counter
    jax.monitoring.unregister_event_duration_listener(counter.on_build)
    jax.monitoring.unregister_event_listener(counter.on_load)


@pytest.mark.parametrize("metric", ["l2", "cos"])
@pytest.mark.parametrize("eviction", ["fifo", "lru"])
def test_no_program_built_after_warmup(small_dataset, graphs, builds,
                                       metric, eviction):
    """After a warm-up, queries that meet miss counts the warm-up never
    met build no program, and every fill has its layer's fixed length."""
    X, _ = small_dataset
    rng = np.random.default_rng(5)
    Q = rng.standard_normal((24, X.shape[1])).astype(np.float32)
    eng = WebANNSEngine(X, graphs[metric], EngineConfig(
        cache_capacity=len(X) // 4, eviction=eviction, metric=metric))
    calls = _record(eng.store)
    for q in Q[:8]:
        eng.search(SearchRequest(query=q, k=10, ef=32))
    warm_counts = {n for _, n in calls}
    n_warm = len(calls)
    built = builds.n
    for q in Q[8:]:
        eng.search(SearchRequest(query=q, k=10, ef=32))
    assert builds.n == built
    window = calls[n_warm:]
    assert {n for _, n in window} - warm_counts  # new miss counts met
    # one list length per layer shape: ef_upper's and ef's miss_cap
    deg = eng.graph.max_degree
    assert {length for length, _ in calls} \
        <= {eng.config.ef_upper + deg + 1, 32 + deg + 1}


def test_cos_single_query_recall(clustered_dataset):
    X, _ = clustered_dataset
    rng = np.random.default_rng(13)
    Q = X[rng.choice(len(X), 12, replace=False)] \
        + 0.3 * rng.standard_normal((12, X.shape[1])).astype(np.float32)
    g = build_hnsw(X, M=8, ef_construction=60, metric="cos", seed=5)
    eng = WebANNSEngine(X, g, EngineConfig(
        cache_capacity=len(X) // 4, metric="cos"))
    got = np.stack([eng.search(SearchRequest(query=q, k=10, ef=32)).ids
                    for q in Q])
    true_ids = brute_force_topk(X, Q, 10, metric="cos")
    assert recall_at_k(got, true_ids) >= 0.9
    assert eng.external.stats.n_db > 0  # tier 3 served misses
