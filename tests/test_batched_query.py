"""Batched phased-lazy driver (DESIGN.md §5): parity + fetch amortization.

The two contracts of the batched query path:

1. **Parity** — ``query_batch(batch_mode="batched")`` returns exactly the
   (ids, dists) of the sequential ``batch_mode="loop"`` driver (which in
   turn equals the in-memory oracle, per test_lazy). Phase boundaries and
   cache trajectories differ between the modes; results may not.
2. **Amortization** — for a batch with overlapping misses, the batched
   driver's total tier-3 accesses (and items fetched) are STRICTLY lower
   than the sequential sum: the union of the B miss lists is deduplicated
   and fetched once per phase.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (BatchStats, EngineConfig, SearchRequest,
                              WebANNSEngine)
from repro.core.hnsw import exact_search
from repro.core.store import cache_init, cache_insert_batch, cache_lookup_batch
from repro.kernels import ref
from repro.kernels.gather_distance import gather_distance_batch_pallas


def tuple_query_batch(eng, Q, k=10, ef=None, batch_mode="batched"):
    """Tuple view of a batched search (the removed shims' shape)."""
    res = eng.search(
        SearchRequest(query=Q, k=k, ef=ef, batch_mode=batch_mode)
    )
    return res.ids, res.dists, res.stats


@pytest.fixture(scope="module")
def overlap_queries(small_dataset):
    """Query batch with deliberate overlap: pairs of near-duplicates, so
    miss lists share ids across the batch."""
    X, Q = small_dataset
    rng = np.random.default_rng(3)
    base = Q[:6]
    dup = base + 0.01 * rng.standard_normal(base.shape).astype(np.float32)
    return np.concatenate([base, dup])  # (12, d)


def _fresh(X, g, cap):
    return WebANNSEngine(X, g, EngineConfig(cache_capacity=cap))


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("ratio", [0.1, 0.3, 1.0])
def test_batched_matches_loop_exactly(small_dataset, small_graph,
                                      overlap_queries, ratio):
    X, _ = small_dataset
    cap = max(16, int(len(X) * ratio))
    loop = _fresh(X, small_graph, cap)
    i1, d1, s1 = tuple_query_batch(loop, overlap_queries, k=10, ef=48,
                                  batch_mode="loop")
    bat = _fresh(X, small_graph, cap)
    i2, d2, s2 = tuple_query_batch(bat, overlap_queries, k=10, ef=48)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)
    assert len(s2) == len(overlap_queries)


def test_batched_eager_mode_parity(small_dataset, small_graph,
                                   overlap_queries):
    """webanns-base (eager, trigger=1) must also be mode-agnostic."""
    X, _ = small_dataset
    cfg = EngineConfig(mode="webanns-base", cache_capacity=128)
    i1, d1, _ = tuple_query_batch(WebANNSEngine(X, small_graph, cfg),
        overlap_queries[:4], k=5, ef=32, batch_mode="loop")
    i2, d2, _ = tuple_query_batch(WebANNSEngine(X, small_graph, cfg),
        overlap_queries[:4], k=5, ef=32)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)


def test_batched_recall_reasonable(clustered_dataset):
    """End-to-end sanity on clustered data: recall vs brute force."""
    from repro.core.hnsw import build_hnsw

    X, Q = clustered_dataset
    g = build_hnsw(X, M=8, ef_construction=60, seed=0)
    eng = WebANNSEngine(X, g, EngineConfig(cache_capacity=len(X) // 4))
    ids, _, _ = tuple_query_batch(eng, Q, k=10, ef=64)
    hits = 0
    for b in range(len(Q)):
        ex, _ = exact_search(X, Q[b], 10)
        hits += len(set(ids[b].tolist()) & set(ex.tolist()))
    assert hits / (10 * len(Q)) > 0.9


# ------------------------------------------------------------ amortization


def test_batched_fewer_tier3_accesses(small_dataset, small_graph,
                                      overlap_queries):
    """Total tier-3 accesses for an overlapping batch: batched < sum of
    the sequential per-query accesses (the headline amortization)."""
    X, _ = small_dataset
    cap = max(16, len(X) // 10)
    loop = _fresh(X, small_graph, cap)
    tuple_query_batch(loop, overlap_queries, k=10, ef=48, batch_mode="loop")
    bat = _fresh(X, small_graph, cap)
    tuple_query_batch(bat, overlap_queries, k=10, ef=48)
    assert bat.external.stats.n_db < loop.external.stats.n_db
    assert bat.external.stats.items_fetched < loop.external.stats.items_fetched
    # whole-batch accounting is exposed and consistent
    bs = bat.last_batch_stats
    assert isinstance(bs, BatchStats)
    assert bs.batch_size == len(overlap_queries)
    assert bs.n_db == bat.external.stats.n_db
    assert bs.n_db_per_query < loop.external.stats.n_db / len(overlap_queries)


def test_per_query_demand_vs_batch_accounting(small_dataset, small_graph,
                                              overlap_queries):
    """Per-query n_db records demand; the sum over queries over-counts the
    shared fetches, i.e. >= the batch's true access count."""
    X, _ = small_dataset
    eng = _fresh(X, small_graph, max(16, len(X) // 10))
    _, _, stats = tuple_query_batch(eng, overlap_queries, k=10, ef=48)
    assert sum(s.n_db for s in stats) >= eng.last_batch_stats.n_db
    assert all(s.n_dist > 0 for s in stats)


def test_per_query_stats_follow_their_queries(small_dataset, small_graph,
                                              overlap_queries):
    """Each query's answer and counts are its own: rolling the batch
    rolls them with it (each phase stages the sorted union of the miss
    lists, so tier 2 evolves the same in either order)."""
    X, _ = small_dataset
    cap = max(16, len(X) // 10)
    runs = []
    for Q in (overlap_queries, np.roll(overlap_queries, 1, axis=0)):
        ids, dists, stats = tuple_query_batch(
            _fresh(X, small_graph, cap), Q, k=10, ef=48
        )
        runs.append((ids, dists, [
            (s.n_hops, s.n_dist, s.n_db, s.items_fetched) for s in stats
        ]))
    (i1, d1, s1), (i2, d2, s2) = runs
    np.testing.assert_array_equal(np.roll(i1, 1, axis=0), i2)
    np.testing.assert_array_equal(np.roll(d1, 1, axis=0), d2)
    assert s2 == s1[-1:] + s1[:-1]
    assert len(set(s1)) > 1  # the counts tell the queries apart


# ------------------------------------------------- batched store primitives


def test_gather_batch_is_one_access(small_dataset, small_graph):
    """A (B, k) gather with overlapping rows costs ONE tier-3 access and
    fetches each unique id exactly once."""
    X, _ = small_dataset
    eng = _fresh(X, small_graph, 32)
    ids = np.array([[1, 2, 3, -1], [3, 2, 7, -1], [7, 1, -1, -1]],
                   np.int32)
    vecs = eng.store.gather_batch(ids)
    assert eng.external.stats.n_db == 1
    assert eng.external.stats.items_fetched == 4  # unique: {1, 2, 3, 7}
    valid = ids >= 0
    np.testing.assert_allclose(vecs[valid], X[ids[valid]], rtol=1e-6)
    assert (vecs[~valid] == 0).all()


def test_cache_lookup_insert_batch_roundtrip():
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((3, 4, 8)).astype(np.float32)
    ids = np.arange(12, dtype=np.int32).reshape(3, 4)
    cache = cache_init(n_items=64, capacity=16, dim=8)
    cache = cache_insert_batch(cache, jnp.asarray(ids), jnp.asarray(vecs))
    present, got = cache_lookup_batch(cache, jnp.asarray(ids))
    assert np.asarray(present).all()
    np.testing.assert_allclose(np.asarray(got), vecs, rtol=1e-6)
    # -1 padded rows report absent
    present, _ = cache_lookup_batch(
        cache, jnp.asarray(np.full((2, 3), -1, np.int32)))
    assert not np.asarray(present).any()


# --------------------------------------------------------- batched kernel


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_gather_distance_batch_kernel_matches_ref(metric):
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((40, 16)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((5, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 40, (5, 9)).astype(np.int32))
    out = gather_distance_batch_pallas(table, ids, Q, metric=metric,
                                       interpret=True)
    want = ref.gather_distance_batch_ref(table, ids, Q, metric)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isinf(np.asarray(out)[np.asarray(ids) < 0]).all()


# ------------------------------------------------------------ serve wiring


def test_scheduler_batches_retrieval(small_dataset, small_graph):
    """Admission waves trigger ONE batched retrieval call for all admitted
    RAG requests; every request gets its ids."""
    from repro.serve.rag import make_batched_retriever
    from repro.serve.scheduler import ContinuousBatcher, Request

    X, Q = small_dataset
    eng = _fresh(X, small_graph, 64)
    calls = []
    retrieve = make_batched_retriever(eng, k=4, ef=32)

    def counting_retrieve(Qm):
        calls.append(len(Qm))
        return retrieve(Qm)

    def decode_fn(params, state, tokens):  # toy LM: echo logits
        B = tokens.shape[0]
        return jnp.zeros((B, 1, 8), jnp.float32), state

    def augment(req):  # ground the prompt in the retrieved context
        return np.concatenate(
            [req.retrieved_ids.astype(np.int32) % 8, req.prompt])

    b = ContinuousBatcher(
        decode_fn=decode_fn, init_state_fn=lambda bs, ln: None,
        params=None, max_batch=4, retrieve_fn=counting_retrieve,
        augment_fn=augment,
    )
    for rid in range(6):
        b.submit(Request(rid=rid, prompt=np.array([1], np.int32),
                         max_new=2, query_vec=Q[rid % len(Q)]))
    done = b.run_until_done()
    assert sorted(done) == list(range(6))
    for r in done.values():
        assert r.retrieved_ids is not None and len(r.retrieved_ids) == 4
        # prompt was rebuilt around the retrieved ids BEFORE prefill
        assert len(r.prompt) == 5 and r.prompt[-1] == 1
    # first wave admits 4 requests in one retrieval; queued ones follow
    assert calls[0] == 4
    assert b.n_retrieval_calls == len(calls) <= 3


def test_rag_pipeline_batch(small_dataset, small_graph):
    from repro.serve.rag import RAGPipeline

    X, _ = small_dataset
    texts = [f"doc-{i}" for i in range(len(X))]
    eng = WebANNSEngine(X, small_graph,
                        EngineConfig(cache_capacity=len(X)), texts=texts)
    eng.warm_cache()

    def embed(q):
        return X[int(q)]

    def tok(q, docs):
        return np.arange(4, dtype=np.int32)[None]

    rag = RAGPipeline(eng, embed, tok, k=4, ef=48)
    outs = rag.batch(["17", "101", "333"])
    assert len(outs) == 3
    for qs, out in zip([17, 101, 333], outs):
        assert qs in out.retrieved_ids.tolist()
        assert out.retrieved_texts[0] is not None
    # single-call path goes through the same batched driver
    one = rag("17")
    assert 17 in one.retrieved_ids.tolist()
