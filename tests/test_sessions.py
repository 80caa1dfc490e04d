"""Multi-tenant session manager (DESIGN.md §11): the leakage contract,
the shared-budget allocator, and the rollback-reserve protocol.

The adversarial core: two tenants whose corpora share the SAME vectors
(and, in engine mode, literally the same id values) must be perfectly
invisible to each other — one tenant's delete/upsert can never change
what the other retrieves, in BOTH isolation modes. On the budget side:
one tenant's traffic may win contested bytes at rebalance time, but can
never evict a peer below its allocated floor between rebalances, and a
rollback climbs by spending the manager's reserve, not a peer's slab.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.cache_opt import (
    QueryTestStats,
    TenantDemand,
    allocate_memory_bytes,
)
from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.metadata import TENANT_COLUMN, Filter, MetadataStore
from repro.core.quant import bytes_per_vector
from repro.serve.sessions import (
    IsolationError,
    SessionManager,
    make_session_retriever,
)

DIM = 16
N = 96
MODES = ("engine", "filter")


def _corpus(seed: int, n: int = N) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, DIM)).astype(np.float32)


def _manager(isolation: str, corpora: dict, budget_frac: float = 2.0,
             **kwargs) -> SessionManager:
    total = sum(len(np.atleast_2d(v[0] if isinstance(v, tuple) else v))
                for v in corpora.values())
    budget = int(total * bytes_per_vector(DIM, "float32") * budget_frac)
    mgr = SessionManager.build(
        corpora, budget_bytes=budget, isolation=isolation,
        M=8, ef_construction=40, shape_grain=16, **kwargs,
    )
    mgr.allocate_equal()  # probe-free split: these tests exercise
    # isolation, not the optimizer (test_allocator_* cover that)
    return mgr


def _flat_ids(res) -> np.ndarray:
    ids = np.asarray(res.ids).ravel()
    return ids[ids >= 0]


# ------------------------------------------------------ leakage contract


@pytest.mark.parametrize("isolation", MODES)
def test_adversarial_shared_vectors_full_isolation(isolation):
    """Tenants 'a' and 'b' hold IDENTICAL corpora. a's deletes and
    upserts must not move b's results by a single id or distance."""
    X = _corpus(0)
    mgr = _manager(isolation, {"a": X.copy(), "b": X.copy()})
    q = X[:5] + 0.1
    req = SearchRequest(query=q, k=6, ef=48)
    before = mgr.search("b", req)
    b_ids_before = set(int(i) for i in mgr.ids_of("b"))

    # a deletes a third of its rows — including rows whose VECTORS are
    # b's nearest neighbors — and upserts others to far-away points
    a_ids = mgr.ids_of("a")
    mgr.delete("a", a_ids[:32])
    mgr.upsert("a", a_ids[32:40], np.full((8, DIM), 50.0, np.float32))

    after = mgr.search("b", req)
    np.testing.assert_array_equal(before.ids, after.ids)
    np.testing.assert_allclose(before.dists, after.dists, rtol=1e-6)
    assert set(int(i) for i in mgr.ids_of("b")) == b_ids_before
    # and a's view did change (the mutations really landed)
    assert len(mgr.ids_of("a")) == len(a_ids) - 32


@pytest.mark.parametrize("isolation", MODES)
def test_search_returns_only_owned_ids(isolation):
    mgr = _manager(isolation, {"a": _corpus(1), "b": _corpus(2)})
    for t in ("a", "b"):
        res = mgr.search(t, SearchRequest(query=_corpus(3)[:8], k=5,
                                          ef=48))
        assert np.isin(_flat_ids(res), mgr.ids_of(t)).all()


def test_cross_tenant_mutation_raises_filter_mode():
    """Filter mode is where foreign ids are addressable at all (one
    shared id space) — delete/upsert on them must refuse outright."""
    mgr = _manager("filter", {"a": _corpus(1), "b": _corpus(2)})
    b_ids = mgr.ids_of("b")
    with pytest.raises(IsolationError, match="does not own"):
        mgr.delete("a", b_ids[:2])
    with pytest.raises(IsolationError, match="does not own"):
        mgr.upsert("a", b_ids[:1],
                   np.zeros((1, DIM), np.float32))
    # nothing landed
    assert len(mgr.ids_of("b")) == len(b_ids)


def test_engine_mode_same_id_values_are_disjoint_rows():
    """In engine mode both tenants legitimately hold id 0 — and they
    are different rows. Deleting a's id 0 leaves b's id 0 live."""
    mgr = _manager("engine", {"a": _corpus(1), "b": _corpus(2)})
    mgr.delete("a", [0])
    assert 0 not in mgr.ids_of("a")
    assert 0 in mgr.ids_of("b")


def test_filter_mode_user_filters_compose_with_tenant_scope():
    Xa, Xb = _corpus(1), _corpus(2)
    meta = {"bucket": ([0] * (N // 2) + [1] * (N - N // 2))}
    mgr = _manager("filter", {"a": (Xa, None, meta),
                              "b": (Xb, None, meta)})
    res = mgr.search("a", SearchRequest(
        query=Xa[3], k=8, ef=48, filter=Filter.eq("bucket", 0),
    ))
    ids = _flat_ids(res)
    assert np.isin(ids, mgr.ids_of("a")).all()
    bucket = mgr.engine_for("a").metadata.column("bucket")
    assert (bucket[ids] == 0).all()


@pytest.mark.parametrize("isolation", MODES)
def test_get_texts_scoped(isolation):
    texts_a = [f"a{i}" for i in range(N)]
    texts_b = [f"b{i}" for i in range(N)]
    mgr = _manager(isolation, {"a": (_corpus(1), texts_a, None),
                               "b": (_corpus(2), texts_b, None)})
    own = mgr.ids_of("a")[:3]
    assert all(t and t.startswith("a") for t in mgr.get_texts("a", own))
    foreign = mgr.ids_of("b")[:3]
    if isolation == "filter":  # engine mode: foreign ids alias own rows
        assert mgr.get_texts("a", foreign) == [None] * 3


# -------------------------------------------------- reserved column rules


def test_reserved_tenant_column_rejected_everywhere():
    mgr = _manager("filter", {"a": _corpus(1)})
    smuggle = {TENANT_COLUMN: [999]}
    with pytest.raises(ValueError, match="reserved"):
        mgr.add("a", np.zeros((1, DIM), np.float32), metadata=smuggle)
    with pytest.raises(ValueError, match="reserved"):
        mgr.upsert("a", mgr.ids_of("a")[:1],
                   np.zeros((1, DIM), np.float32), metadata=smuggle)
    with pytest.raises(ValueError, match="reserved"):
        SessionManager.build(
            {"x": (np.zeros((4, DIM), np.float32), None, smuggle)},
            budget_bytes=1 << 16,
        )
    # the store itself refuses dunder introduction without the flag
    with pytest.raises(ValueError, match="reserved"):
        MetadataStore({TENANT_COLUMN: [1, 2]})
    with pytest.raises(ValueError, match="reserved"):
        WebANNSEngine.build(
            np.zeros((4, DIM), np.float32), M=4, ef_construction=8,
            metadata={TENANT_COLUMN: [1, 2, 3, 4]},
        )


def test_upsert_inherit_keeps_tenant_stamp():
    """engine.upsert inherits retired rows' metadata — including the
    reserved column (the extend-but-not-introduce exemption). The
    replacement rows must carry the SAME tenant code."""
    mgr = _manager("filter", {"a": _corpus(1), "b": _corpus(2)})
    eng = mgr.engine_for("a")
    old = mgr.ids_of("a")[:2]
    res = mgr.upsert("a", old, np.ones((2, DIM), np.float32))
    col = eng.metadata.column(TENANT_COLUMN)
    code_a = mgr._codes["a"]
    assert (col[res.ids] == code_a).all()
    assert np.isin(res.ids, mgr.ids_of("a")).all()


def test_tenant_codes_start_at_one():
    """Code 0 is the int column fill value = 'unowned'; a tenant whose
    code collided with it would own every fill-stamped row."""
    mgr = _manager("filter", {"a": _corpus(1)})
    assert min(mgr._codes.values()) >= 1


# ------------------------------------------------- budget + access stats


def test_tenant_stats_attribution():
    # tight budget → partial caches → the search must touch tier 3
    mgr = _manager("engine", {"a": _corpus(1), "b": _corpus(2)},
                   budget_frac=0.25)
    mgr.search("a", SearchRequest(query=_corpus(3)[:4], k=5, ef=48))
    assert mgr.stats["a"].queries == 4
    assert mgr.stats["a"].n_db > 0  # cold cache → tier-3 traffic
    assert mgr.stats["b"].queries == 0
    assert mgr.stats["b"].n_db == 0


def test_traffic_storm_cannot_evict_peer_engine_mode():
    """The floor guarantee (engine mode): tenant a hammering its slice
    does not touch b's cache — b's next query after the storm costs
    ZERO tier-3 accesses if it cost zero before (fully warm and
    untouched), and b's allocated capacity is unchanged."""
    mgr = _manager("engine", {"a": _corpus(1), "b": _corpus(2)},
                   budget_frac=2.0)
    cap_b = mgr.engine_for("b").store.capacity
    # warm b fully (capacity covers the corpus at this budget)
    mgr.engine_for("b").warm_cache()
    q = _corpus(3)
    before = dataclasses.replace(mgr.stats["b"])
    mgr.search("b", SearchRequest(query=q[0], k=5, ef=48))
    warm_cost = mgr.stats["b"].n_db - before.n_db
    assert warm_cost == 0  # fully warm baseline
    for i in range(20):  # the storm
        mgr.search("a", SearchRequest(query=q[i % len(q)], k=5, ef=48))
    after_storm = dataclasses.replace(mgr.stats["b"])
    mgr.search("b", SearchRequest(query=q[0], k=5, ef=48))
    assert mgr.stats["b"].n_db - after_storm.n_db == 0
    assert mgr.engine_for("b").store.capacity == cap_b
    assert mgr._alloc_items["b"] >= mgr.shape_grain


def test_rollback_spends_reserve_never_peers():
    """A forced n_db regression for tenant a grows a's slab out of the
    RESERVE; b's allocation and capacity are untouched. A dry reserve
    grants nothing (and still never shrinks b)."""
    from repro.core.cache_opt import RollbackManager

    mgr = _manager("engine", {"a": _corpus(1), "b": _corpus(2)},
                   budget_frac=2.0)
    # hand-build a ladder: operating rung 16 items, climb target 48
    mgr._alloc_items["a"] = 16
    mgr._rollbacks["a"] = RollbackManager(
        [(48, 0.5), (16, 0.5)], resize=mgr._make_rollback_resize("a")
    )
    mgr._reserve_bytes = 64 * bytes_per_vector(DIM, "float32")
    b_items = mgr._alloc_items["b"]
    cap_b = mgr.engine_for("b").store.capacity
    reserve0 = mgr._reserve_bytes

    assert mgr._rollbacks["a"].observe(10.0)  # n_db 10 > θ 0.5 → climb
    assert mgr._alloc_items["a"] == 48
    assert mgr._reserve_bytes == reserve0 - 32 * bytes_per_vector(
        DIM, "float32"
    )
    assert mgr._alloc_items["b"] == b_items
    assert mgr.engine_for("b").store.capacity == cap_b
    assert mgr.stats["a"].rollbacks == 1
    events = [e for e in mgr.allocation_history
              if e["event"] == "rollback"]
    assert len(events) == 1 and events[0]["tenant"] == "a"

    # dry reserve: a second regression wants more but gets nothing
    mgr._reserve_bytes = 0
    mgr._rollbacks["a"] = RollbackManager(
        [(96, 0.5), (48, 0.5)], resize=mgr._make_rollback_resize("a")
    )
    mgr._rollbacks["a"].observe(10.0)
    assert mgr._alloc_items["a"] == 48  # no grant
    assert mgr._alloc_items["b"] == b_items


# ----------------------------------------------------- allocator (pure)


def _fake_demand(tenant: str, n_items: int, traffic: float,
                 hard: float = 200.0) -> TenantDemand:
    """Synthetic tenant: n_db falls as C grows (hyperbola-ish), with
    fixed in-memory time — no engine, no jax, so the allocator's
    arithmetic is tested in isolation."""

    def query_test(c: int) -> QueryTestStats:
        n_db = max(1.0, hard / max(c, 1))
        return QueryTestStats(
            n_db=n_db, n_q=64.0, t_query=0.005 + n_db * 0.01, t_db=0.01
        )

    return TenantDemand(
        tenant=tenant, query_test=query_test, dim=DIM,
        n_items=n_items, traffic=traffic, min_items=16,
    )


def test_allocator_uncontended_grants_optima_plus_surplus():
    bpi = bytes_per_vector(DIM, "float32")
    demands = [_fake_demand("a", 512, 1.0), _fake_demand("b", 512, 1.0)]
    # 2x both corpora, so optima fit even after the 10% reserve
    alloc = allocate_memory_bytes(
        demands, budget_bytes=4 * 512 * bpi, shape_grain=16,
    )
    assert not alloc.contended
    for a in alloc.allocations.values():
        assert a.c_items >= a.c_opt
        assert a.satisfied
    assert alloc.total_alloc_bytes <= alloc.budget_bytes


def test_allocator_uncontended_surplus_snaps_down_within_budget():
    """A surplus that ends mid-grain is snapped down, never up past the
    byte budget."""
    bpi = bytes_per_vector(DIM, "float32")
    budget = 1000 * bpi  # 62.5 grains of 16 items
    alloc = allocate_memory_bytes(
        [_fake_demand("a", 8192, 1.0)], budget, reserve_frac=0.0,
        shape_grain=16,
    )
    assert not alloc.contended
    assert alloc.allocations["a"].c_items == 62 * 16
    assert alloc.total_alloc_bytes <= budget


def test_allocator_contended_respects_budget_and_floors():
    bpi = bytes_per_vector(DIM, "float32")
    demands = [_fake_demand("a", 512, 3.0, hard=5000.0),
               _fake_demand("b", 512, 1.0, hard=5000.0)]
    budget = 256 * bpi  # far below the two optima
    alloc = allocate_memory_bytes(demands, budget, shape_grain=16)
    assert alloc.contended
    assert alloc.total_alloc_bytes <= budget
    for a in alloc.allocations.values():
        assert a.c_items >= 16  # floor
        assert a.c_items <= a.c_opt or a.c_items <= 16
    # traffic decides who wins contested bytes
    assert (alloc.allocations["a"].c_items
            >= alloc.allocations["b"].c_items)


def test_allocator_traffic_shift_moves_bytes():
    bpi = bytes_per_vector(DIM, "float32")
    budget = 256 * bpi

    def run(w_a: float, w_b: float):
        return allocate_memory_bytes(
            [_fake_demand("a", 512, w_a, hard=5000.0),
             _fake_demand("b", 512, w_b, hard=5000.0)],
            budget, shape_grain=16,
        ).items()

    even = run(1.0, 1.0)
    skew = run(8.0, 1.0)
    assert skew["a"] > even["a"]
    assert skew["b"] <= even["b"]


def test_allocator_ladder_anchored_at_allocation():
    bpi = bytes_per_vector(DIM, "float32")
    alloc = allocate_memory_bytes(
        [_fake_demand("a", 512, 1.0, hard=5000.0)],
        budget_bytes=128 * bpi, shape_grain=16,
    )
    ladder = alloc.allocations["a"].ladder
    assert ladder[-1][0] == alloc.allocations["a"].c_items
    assert all(c > alloc.allocations["a"].c_items
               for c, _ in ladder[:-1])
    # descending capacities
    caps = [c for c, _ in ladder]
    assert caps == sorted(caps, reverse=True)


def test_allocator_rejects_duplicates_and_bad_budget():
    d = _fake_demand("a", 64, 1.0)
    with pytest.raises(ValueError, match="duplicate"):
        allocate_memory_bytes([d, _fake_demand("a", 64, 1.0)], 1 << 16)
    with pytest.raises(ValueError, match="budget_bytes"):
        allocate_memory_bytes([d], 0)


# --------------------------------------------- manager-level allocation


@pytest.mark.parametrize("isolation", MODES)
def test_manager_allocate_and_rebalance_trace(isolation):
    """Full probe-driven allocation through the manager: the trace
    records the split, and a rebalance under a shifted traffic mix
    re-runs the allocator with the new weights recorded."""
    mgr = _manager(isolation, {"a": _corpus(1), "b": _corpus(2)},
                   budget_frac=0.5)
    alloc = mgr.allocate()
    assert set(alloc.items()) == {"a", "b"}
    total_bytes = sum(
        alloc.allocations[t].alloc_bytes for t in ("a", "b")
    )
    assert total_bytes <= mgr.budget_bytes
    assert mgr._rollbacks  # ladders installed
    ev0 = [e for e in mgr.allocation_history
           if e["event"] == "allocate"][-1]
    assert ev0["traffic"] == {"a": 1.0, "b": 1.0}

    mgr.rebalance(traffic={"a": 9.0, "b": 1.0})
    ev1 = [e for e in mgr.allocation_history
           if e["event"] == "allocate"][-1]
    assert ev1["traffic"] == {"a": 9.0, "b": 1.0}
    assert mgr.stats["a"].window_queries == 0  # window reset


def test_unknown_tenant_and_mode_rejected():
    mgr = _manager("engine", {"a": _corpus(1)})
    with pytest.raises(KeyError, match="unknown tenant"):
        mgr.search("ghost", SearchRequest(query=np.zeros(DIM)))
    with pytest.raises(ValueError, match="isolation mode"):
        SessionManager(budget_bytes=1 << 20, isolation="vpc")
    with pytest.raises(ValueError, match="already exists"):
        mgr.create_tenant("a", _corpus(1))


# --------------------------------------- batcher integration (retrieval)


def test_session_retriever_scopes_rag_requests():
    """make_session_retriever through the ContinuousBatcher: each RAG
    request retrieves ONLY from its own tenant's slice, through one
    batched tenant-scoped search per tenant per admission wave."""
    import jax
    import jax.numpy as jnp

    from repro.serve.scheduler import ContinuousBatcher, Request

    mgr = _manager("filter", {"a": _corpus(1), "b": _corpus(2)})
    retrieve = make_session_retriever(mgr, k=3, ef=48)

    def decode_fn(params, state, tokens, positions, active):
        B, L = state.shape
        state = state.at[jnp.arange(B),
                         jnp.where(active, positions, L)].set(
            tokens[:, 0], mode="drop")
        logits = jax.nn.one_hot(tokens[:, 0] % 7, 7)[:, None, :]
        return logits, state

    b = ContinuousBatcher(
        decode_fn=decode_fn,
        init_state_fn=lambda bs, ln: jnp.zeros((bs, ln), jnp.int32),
        params=None, max_batch=4, max_len=16,
        retrieve_fn=retrieve,
    )
    q = _corpus(3)
    for rid, tenant in enumerate(["a", "b", "a", "b"]):
        b.submit(Request(
            rid=rid, prompt=np.array([1, 2], np.int32), max_new=2,
            query_vec=q[rid], tenant=tenant,
        ))
    done = b.run_until_done()
    assert sorted(done) == [0, 1, 2, 3]
    for rid, tenant in enumerate(["a", "b", "a", "b"]):
        got = done[rid].retrieved_ids
        got = got[got >= 0]
        assert got.size and np.isin(got, mgr.ids_of(tenant)).all()
    # a tenant-less RAG request through a session retriever must fail
    # loudly, not silently search some default slice
    b2 = ContinuousBatcher(
        decode_fn=decode_fn,
        init_state_fn=lambda bs, ln: jnp.zeros((bs, ln), jnp.int32),
        params=None, max_batch=2, max_len=16,
        retrieve_fn=retrieve,
    )
    b2.submit(Request(rid=0, prompt=np.array([1], np.int32),
                      max_new=1, query_vec=q[0]))
    with pytest.raises(ValueError, match="tenant"):
        b2.run_until_done()


# --------------------------------------- mixed-precision budget (§12)


def test_allocator_charges_pq_tenant_m_bytes_per_item():
    """A precision='pq' tenant costs M bytes/item in the shared budget,
    not dim+4 — the allocator must not over-charge it 8x."""
    demands = [
        dataclasses.replace(_fake_demand("pq_t", 512, 1.0),
                            precision="pq", n_subspaces=8),
        dataclasses.replace(_fake_demand("i8_t", 512, 1.0),
                            precision="int8"),
    ]
    alloc = allocate_memory_bytes(
        demands, budget_bytes=1 << 16, shape_grain=16)
    assert alloc.allocations["pq_t"].bytes_per_item == 8
    assert alloc.allocations["i8_t"].bytes_per_item == DIM + 4
    assert alloc.total_alloc_bytes <= alloc.budget_bytes


def test_session_manager_mixed_pq_int8_budget():
    """One budget, a pq tenant and an int8 tenant (per-tenant configs):
    the manager books each at its own bytes/item and both keep serving
    with full isolation."""
    mgr = SessionManager.build(
        {"pq_t": _corpus(1), "i8_t": _corpus(2)},
        budget_bytes=int(2 * N * bytes_per_vector(DIM, "float32")),
        isolation="engine", M=8, ef_construction=40, shape_grain=16,
        configs={
            "pq_t": EngineConfig(precision="pq", pq_subspaces=8,
                                 rerank_alpha=4.0),
            "i8_t": EngineConfig(precision="int8"),
        },
    )
    assert mgr._bpi("pq_t") == 8
    assert mgr._bpi("i8_t") == DIM + 4
    assert mgr.engine_for("pq_t").config.precision == "pq"
    alloc = mgr.allocate()
    assert alloc.allocations["pq_t"].bytes_per_item == 8
    assert alloc.allocations["i8_t"].bytes_per_item == DIM + 4
    assert alloc.total_alloc_bytes <= mgr.budget_bytes
    # the pq tenant's byte bill reflects codes, not scalar rows
    a = alloc.allocations["pq_t"]
    assert a.alloc_bytes == a.c_items * 8
    # both serve, ownership intact
    for t, seed in (("pq_t", 1), ("i8_t", 2)):
        res = mgr.search(t, SearchRequest(
            query=_corpus(seed)[0], k=5, ef=32))
        got = _flat_ids(res)
        assert got.size and np.isin(got, mgr.ids_of(t)).all()


def test_per_tenant_config_rejected_in_filter_mode():
    mgr = SessionManager(budget_bytes=1 << 20, isolation="filter")
    with pytest.raises(ValueError, match="isolation='engine'"):
        mgr.create_tenant("a", _corpus(1),
                          config=EngineConfig(precision="pq"))
    with pytest.raises(ValueError, match="isolation='engine'"):
        SessionManager.build(
            {"a": _corpus(1), "b": _corpus(2)},
            budget_bytes=1 << 20, isolation="filter",
            configs={"a": EngineConfig(precision="pq")},
        )
