"""Heuristic cache-size optimization (Algorithm 2) + Eq. 3/4 validation."""


import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.cache_opt import (
    QueryTestStats,
    RollbackManager,
    get_theta,
    n_db_optimal,
    n_db_random,
    optimize_memory_size,
    simulate_n_db,
)
from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine


def test_eq3_random_fetch_closed_form():
    """Empirical n_db under the random-fetch model ≈ Eq. 3 (±7%)."""
    rng = np.random.default_rng(0)
    n, n_q = 500, 80
    path = rng.choice(n, n_q, replace=False)
    for n_mem in (50, 150, 300, 450):
        trials = [
            simulate_n_db(path, n, n_mem, "random",
                          np.random.default_rng(s))
            for s in range(30)
        ]
        emp = float(np.mean(trials))
        pred = n_db_random(n_mem, n_q, n)
        assert abs(emp - pred) / pred < 0.07, (n_mem, emp, pred)


def test_eq4_optimal_fetch_closed_form():
    """Optimal prefetch matches Eq. 4 exactly for a distinct-item path."""
    n, n_q = 500, 96
    path = np.arange(n_q)
    for n_mem in (7, 16, 32, 48, 96, 200):
        emp = simulate_n_db(path, n, n_mem, "optimal")
        assert emp == n_db_optimal(n_mem, n_q), (n_mem, emp)


def test_random_worse_than_optimal():
    rng = np.random.default_rng(1)
    path = rng.choice(1000, 100, replace=False)
    for n_mem in (50, 200, 500):
        r = simulate_n_db(path, 1000, n_mem, "random")
        o = simulate_n_db(path, 1000, n_mem, "optimal")
        assert o <= r


def test_get_theta_combines_both_methods():
    # percentage binds
    assert get_theta(0.5, 10.0, 1.0, 0.01) == pytest.approx(50.0)
    # absolute binds
    assert get_theta(0.9, 0.05, 1.0, 0.01) == pytest.approx(5.0)


def test_algorithm2_on_synthetic_curve():
    """Drive Algorithm 2 against a synthetic fetch curve lying between the
    random line and the optimal hyperbola; it must stop at a C where
    n_db <= θ and the next probed C exceeded θ."""
    n, n_q = 1000, 120
    t_in, t_db = 1e-4, 1e-2

    def curve(c):  # halfway between optimal and random
        return 0.5 * (n_db_optimal(c, n_q) + n_db_random(c, n_q, n))

    probed = []

    def query_test(c):
        probed.append(c)
        ndb = curve(c)
        return QueryTestStats(
            n_db=ndb, n_q=n_q, t_query=n_q * t_in + ndb * t_db, t_db=t_db
        )

    res = optimize_memory_size(query_test, c0=n, p=0.8, t_theta=0.5)
    assert res.c_best < n  # it did shrink
    theta_best = [s.theta for s in res.steps if s.c == res.c_best][0]
    assert curve(res.c_best) <= theta_best
    # strictly decreasing probes → convergence
    assert all(a > b for a, b in zip(probed, probed[1:]))


def test_algorithm2_keeps_c0_when_already_over():
    def query_test(c):
        return QueryTestStats(n_db=1000.0, n_q=10, t_query=1.0, t_db=0.01)

    res = optimize_memory_size(query_test, c0=100, p=0.1, t_theta=0.01)
    assert res.c_best == 100
    assert len(res.ladder) == 0 or res.ladder[0][0] == 100


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(200, 2000),
    n_q=st.integers(10, 150),
    p=st.floats(0.1, 0.95),
)
def test_property_algorithm2_always_terminates_and_safe(n, n_q, p):
    n_q = min(n_q, n)

    def query_test(c):
        ndb = n_db_random(c, n_q, n)
        return QueryTestStats(
            n_db=ndb, n_q=n_q, t_query=n_q * 1e-4 + ndb * 1e-2, t_db=1e-2
        )

    res = optimize_memory_size(query_test, c0=n, p=p, t_theta=0.2)
    assert 1 <= res.c_best <= n
    # accepted size satisfies its own theta
    for step in res.steps:
        if step.accepted:
            assert step.stats.n_db <= step.theta + 1e-9


def test_rollback_manager():
    sizes = []
    ladder = [(100, 50.0), (60, 40.0), (30, 20.0)]
    rm = RollbackManager(ladder, resize=sizes.append)
    assert rm.current == (30, 20.0)
    assert not rm.observe(10.0)  # fine
    assert rm.observe(25.0)  # exceeds θ=20 → roll back to 60
    assert rm.current == (60, 40.0)
    assert sizes == [60]
    assert rm.observe(45.0)  # exceeds θ=40 → roll back to 100
    assert rm.current == (100, 50.0)
    assert not rm.observe(1e9)  # at C0 already; stays
    assert sizes == [60, 100]


def test_algorithm2_end_to_end_on_engine(small_dataset, small_graph):
    """Full integration: optimizer shrinks the real engine's cache while
    holding n_db under θ on the probe queries."""
    X, Q = small_dataset
    eng = WebANNSEngine(X, small_graph, EngineConfig(cache_capacity=len(X)))

    def query_test(c):
        eng.resize_cache(c)
        eng.warm_cache()
        stats = []
        for q in Q[:4]:
            res = eng.search(SearchRequest(query=q, k=10, ef=48))
            stats.append(res.stats)
        n_db = float(np.mean([s.n_db for s in stats]))
        n_q = float(np.mean([s.n_visited for s in stats]))
        t_q = float(np.mean([s.t_query for s in stats]))
        t_db = eng.external.access_cost(16)
        return QueryTestStats(n_db=n_db, n_q=n_q, t_query=t_q, t_db=t_db)

    res = optimize_memory_size(query_test, c0=len(X), p=0.8, t_theta=0.1)
    assert 1 <= res.c_best <= len(X)
    assert res.c_best < len(X)  # warm full-size cache needs no accesses →
    # optimizer must discover it can shrink
    assert len(res.steps) >= 2
