"""The lazy drivers' phase loops: one program and one read a phase
(DESIGN.md §5).

Each layer runs one program at its entry (seed and first beam phase)
and one per gathered phase (fill, load and the next beam phase), and
reads each program's miss list once. The reference below is the loop
those programs replaced: per phase a beam-phase program and a read of
its miss count, and per gathered phase a read of the miss ids,
``TieredStore.fill`` / ``fill_batch`` and a load program. Two
contracts:

1. **Same work** — over a sequence of cold requests, the loops return
   the ids and distances, count the tier-3 accesses, rows and tier-2
   hits and misses, and leave tier 2's ``slot_of``, ``id_of`` and clock
   that the reference does, after every request; and read the device
   exactly once fewer per gathered phase.
2. **Programs** — exactly one phase-loop program per layer entry and per
   gathered phase.

:func:`use_reference_loop` also serves the other fill tests, as the
loop through which a fill can be swapped for the general ``gather``.
"""

import functools
import types

import jax
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import search as S
from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.metadata import Filter
from repro.core.spans import to_host
from repro.core.store import EVICT_LRU, cache_lookup, cache_touch

# ------------------------------------------------- the reference loop


@functools.partial(jax.jit, static_argnames=("ef", "miss_cap", "metric"))
def _seed_cached(q, entry_ids, cache, ef, miss_cap, metric, tombs, banned):
    n = cache.slot_of.shape[0]
    state = S.make_state(ef, miss_cap, n, tombstones=tombs, banned=banned)
    lookup = lambda ids: cache_lookup(cache, ids)
    return S.seed_state(state, q, entry_ids, lookup, metric)


@functools.partial(jax.jit, static_argnames=("metric", "ef_trigger"))
def _phase_cached(q, neighbors_l, state, cache, metric, ef_trigger):
    lookup = lambda ids: cache_lookup(cache, ids)
    return S.search_phase(
        q, neighbors_l, state, lookup, metric, ef_trigger=ef_trigger
    )


@functools.partial(jax.jit, static_argnames=("metric",))
def _load_cached(q, state, loaded_ids, loaded_vecs, metric):
    return S.load_phase(q, state, loaded_ids, loaded_vecs, metric)


@functools.partial(jax.jit, static_argnames=("ef", "miss_cap", "metric"))
def _batch_seed_cached(Q, entry_ids, cache, ef, miss_cap, metric, tombs,
                       banned):
    n = cache.slot_of.shape[0]
    lookup = lambda ids: cache_lookup(cache, ids)
    states = S.batch_make_state(
        Q.shape[0], ef, miss_cap, n, tombstones=tombs, banned=banned
    )
    return S.batch_seed_state(states, Q, entry_ids, lookup, metric)


@functools.partial(jax.jit, static_argnames=("metric", "ef_trigger"))
def _batch_phase_cached(Q, neighbors_l, states, cache, metric, ef_trigger):
    lookup = lambda ids: cache_lookup(cache, ids)
    return S.batch_search_phase(
        Q, neighbors_l, states, lookup, metric, ef_trigger=ef_trigger
    )


@functools.partial(jax.jit, static_argnames=("metric",))
def _batch_load_cached(Q, states, loaded_ids, loaded_vecs, metric):
    return S.batch_load_phase(Q, states, loaded_ids, loaded_vecs, metric)


def _reference_lazy_layer(self, q, layer, entry, ef, stats, eager,
                          banned=None):
    """The single-query layer as three programs a phase."""
    cfg = self.config
    acc = self.external.stats
    miss_cap = ef + self.graph.max_degree + 1
    state = _seed_cached(
        q, entry, self.store.cache, ef, miss_cap, cfg.metric,
        self._tombs_device(),
        self._noban_device() if banned is None else banned,
    )
    trigger = 1 if eager else ef
    for _ in range(cfg.max_phases):
        state = _phase_cached(
            q, self._layer_neighbors[layer], state, self.store.cache,
            cfg.metric, trigger,
        )
        mc = int(to_host(state.miss_count, acc))
        if self.store.eviction == EVICT_LRU:
            self.store.cache = cache_touch(self.store.cache, state.beam.ids)
        acc.tier2_misses += mc
        if mc == 0:
            break
        miss_np = to_host(state.miss_ids, acc)
        db0, fetched0 = acc.n_db, acc.items_fetched
        vecs = self.store.fill(miss_np)
        stats.n_db += acc.n_db - db0
        stats.items_fetched += acc.items_fetched - fetched0
        state = _load_cached(q, state, state.miss_ids, vecs, cfg.metric)
    return state


def _reference_batched_lazy_layer(self, Q, layer, entry_ids, ef, per_stats,
                                  bstats, eager, banned=None):
    """The batched layer as three programs a phase."""
    cfg = self.config
    acc = self.external.stats
    miss_cap = ef + self.graph.max_degree + 1
    trigger = 1 if eager else ef
    if banned is None:
        banned = jax.numpy.broadcast_to(
            self._noban_device(), (Q.shape[0], self.n)
        )
    states = _batch_seed_cached(
        Q, jax.numpy.asarray(entry_ids), self.store.cache, ef, miss_cap,
        cfg.metric, self._tombs_device(), banned,
    )
    for _ in range(cfg.max_phases):
        states = _batch_phase_cached(
            Q, self._layer_neighbors[layer], states, self.store.cache,
            cfg.metric, trigger,
        )
        mc = to_host(states.miss_count, acc)
        if self.store.eviction == EVICT_LRU:
            self.store.cache = cache_touch(
                self.store.cache, states.beam.ids.reshape(-1)
            )
        n_miss = int(mc.sum())
        acc.tier2_misses += n_miss
        if n_miss == 0:
            break
        miss_np = to_host(states.miss_ids, acc)
        db0, fetched0 = acc.n_db, acc.items_fetched
        vecs = self.store.fill_batch(miss_np)
        bstats.n_db += acc.n_db - db0
        bstats.items_fetched += acc.items_fetched - fetched0
        bstats.n_phases += 1
        for b in np.nonzero(mc > 0)[0]:
            per_stats[b].n_db += 1
            per_stats[b].items_fetched += int(mc[b])
        states = _batch_load_cached(
            Q, states, states.miss_ids, vecs, cfg.metric
        )
    return states


def use_reference_loop(engine):
    """Run ``engine``'s lazy layers through the reference loop, which
    calls ``engine.store.fill`` / ``fill_batch`` once a gathered phase."""
    engine._lazy_layer = types.MethodType(_reference_lazy_layer, engine)
    engine._batched_lazy_layer = types.MethodType(
        _reference_batched_lazy_layer, engine
    )
    return engine


# ------------------------------------------------------------- the tests

N_USERS = 3
# (B, filtered, mode): plain, filtered and eager, one query or sixteen
SHAPES = {
    "b1-plain": (1, False, "webanns"),
    "b1-filtered": (1, True, "webanns"),
    "b1-base": (1, False, "webanns-base"),
    "b16-plain": (16, False, "webanns"),
    "b16-filtered": (16, True, "webanns"),
    "b16-base": (16, False, "webanns-base"),
}


def _engine(X, g, eviction, mode):
    return WebANNSEngine(X, g, EngineConfig(
        cache_capacity=len(X) // 4, eviction=eviction, mode=mode,
    ), metadata={"user": np.arange(len(X)) % N_USERS})


def _requests(X, B, filtered, n=3):
    rng = np.random.default_rng(17)
    out = []
    for r in range(n):
        Q = rng.standard_normal((max(B, 1), X.shape[1])).astype(np.float32)
        if B == 1:
            filt = Filter.eq("user", r % N_USERS) if filtered else None
            out.append(SearchRequest(query=Q[0], k=10, ef=32, filter=filt))
        else:
            # per-query filters, some absent: a (B, N) deny matrix
            filt = [Filter.eq("user", b % N_USERS) if b % 4 else None
                    for b in range(B)] if filtered else None
            out.append(SearchRequest(query=Q, k=10, ef=32, filter=filt))
    return out


def _tier2(engine):
    c = engine.store.cache
    return [np.asarray(x) for x in (c.slot_of, c.id_of, c.clock)]


@pytest.mark.parametrize("eviction", ["fifo", "lru"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_loops_do_the_reference_work(small_dataset, small_graph, eviction,
                                     shape):
    X, _ = small_dataset
    B, filtered, mode = SHAPES[shape]
    ref = use_reference_loop(_engine(X, small_graph, eviction, mode))
    eng = _engine(X, small_graph, eviction, mode)
    for req in _requests(X, B, filtered):
        a0, b0 = ref.snapshot_access_stats(), eng.snapshot_access_stats()
        want, got = ref.search(req), eng.search(req)
        np.testing.assert_array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(got.dists, want.dists)
        dw = {k: v - a0[k] for k, v in ref.snapshot_access_stats().items()}
        dg = {k: v - b0[k] for k, v in eng.snapshot_access_stats().items()}
        for key in ("n_db", "items_fetched", "items_used", "tier2_misses",
                    "tier2_hits"):
            assert dg[key] == dw[key], key
        assert dg["n_db"] > 0
        # one read fewer a gathered phase: the miss count's
        assert dg["host_syncs"] == dw["host_syncs"] - dg["n_db"]
        stats = got.stats if B > 1 else [got.stats]
        ref_stats = want.stats if B > 1 else [want.stats]
        for s, w in zip(stats, ref_stats):
            assert (s.n_db, s.items_fetched, s.n_dist, s.n_hops) \
                == (w.n_db, w.items_fetched, w.n_dist, w.n_hops)
        if B > 1:
            assert got.batch_stats.n_phases == want.batch_stats.n_phases
        for name, x, y in zip(("slot_of", "id_of", "clock"),
                              _tier2(eng), _tier2(ref)):
            np.testing.assert_array_equal(x, y, err_msg=name)


@pytest.mark.parametrize("batched", [True, False], ids=["b4", "b1"])
def test_one_program_per_layer_entry_and_gathered_phase(
        small_dataset, small_graph, monkeypatch, batched):
    X, Q = small_dataset
    eng = _engine(X, small_graph, "lru", "webanns")
    calls = {"_enter_layer": 0, "_gathered_phase": 0}

    def counted(name):
        program = getattr(engine_mod, name)

        def run(*args, **kwargs):
            calls[name] += 1
            return program(*args, **kwargs)
        return run

    for name in calls:
        monkeypatch.setattr(engine_mod, name, counted(name))
    for method in ("fill", "fill_batch"):  # the device half runs inside
        monkeypatch.setattr(eng.store, method, None)
    for q in (Q[:4], Q[4:8]) if batched else (Q[0], Q[1]):
        before = eng.snapshot_access_stats()
        calls.update(dict.fromkeys(calls, 0))
        res = eng.search(SearchRequest(query=q, k=10, ef=32))
        n_db = eng.snapshot_access_stats()["n_db"] - before["n_db"]
        syncs = eng.snapshot_access_stats()["host_syncs"] \
            - before["host_syncs"]
        assert calls["_enter_layer"] == small_graph.n_layers
        assert calls["_gathered_phase"] == n_db > 0
        if batched:
            assert n_db == res.batch_stats.n_phases
        # a read a program, then the final counts and answer (the
        # batched driver also reads three arrays between layers)
        tail = 3 * small_graph.n_layers + 1 if batched else 3
        assert syncs == calls["_enter_layer"] \
            + calls["_gathered_phase"] + tail
