"""Host spans and window counters of the lazy drivers (DESIGN.md §5).

The spans are read back from a profiler trace recorded on the CPU
backend, where ``TraceAnnotation`` events land on the ``/host:CPU``
plane just as they do beside the device's programs on a TPU host.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import store as store_mod
from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.spans import SPAN_NAMES
from repro.core.store import AccessStats
from test_phase_loops import use_reference_loop

COUNTERS = ("tier2_hits", "tier2_misses", "host_syncs")
INSIDE_SEARCH = ("seed", "beam_phase", "tier2_gather", "descend",
                 "finalize")


def _engine(X, g, cap, **cfg):
    return WebANNSEngine(X, g, EngineConfig(cache_capacity=cap, **cfg))


def _delta(eng, before):
    after = eng.snapshot_access_stats()
    return {key: after[key] - before[key] for key in after}


def _host_spans(log_dir):
    """(name, start_ns, end_ns) of every program span in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name in SPAN_NAMES]
    return out


def _within(inner, outers):
    _, s, e = inner
    return any(s >= os_ and e <= oe for _, os_, oe in outers)


def _named(spans, name, within=None):
    return [sp for sp in spans if sp[0] == name
            and (within is None or _within(sp, [within]))]


@pytest.fixture(scope="module")
def traced(small_dataset, small_graph, tmp_path_factory):
    """One batched and one single-query search of a cold engine (tier 2
    holds N/4) under the profiler, per precision, after a warm-up engine
    has compiled every program they run."""
    X, Q = small_dataset
    cap = len(X) // 4
    requests = (SearchRequest(query=Q[:4], k=10, ef=32),
                SearchRequest(query=Q[4], k=10, ef=32))
    out = {}
    for precision in ("float32", "int8"):
        warm = _engine(X, small_graph, cap, precision=precision)
        for req in requests:
            warm.search(req)
        eng = _engine(X, small_graph, cap, precision=precision)
        before = eng.snapshot_access_stats()
        log_dir = str(tmp_path_factory.mktemp(f"trace-{precision}"))
        jax.profiler.start_trace(log_dir)
        res_b = [eng.search(req) for req in requests][0]
        jax.profiler.stop_trace()
        out[precision] = (_host_spans(log_dir), res_b, _delta(eng, before))
    return out


@pytest.mark.parametrize("precision", ["float32", "int8"])
def test_every_span_is_written_and_nested_in_its_search(traced, precision):
    spans, res_b, delta = traced[precision]
    names = {sp[0] for sp in spans}
    expected = set(SPAN_NAMES) - {"rerank"}
    assert expected <= names
    searches = _named(spans, "search")
    assert len(searches) == 2
    for sp in spans:
        if sp[0] in INSIDE_SEARCH:
            assert _within(sp, searches), sp
    gathers = _named(spans, "tier2_gather")
    reranks = _named(spans, "rerank")
    for sp in _named(spans, "tier3_fetch"):
        assert _within(sp, gathers + reranks), sp
    batched = min(searches, key=lambda sp: sp[1])  # issued first
    assert len(_named(spans, "tier2_gather", within=batched)) \
        == res_b.batch_stats.n_phases
    assert len(_named(spans, "tier3_fetch")) == delta["n_db"]
    if precision == "int8":
        for search in searches:
            assert len(_named(spans, "rerank", within=search)) == 1
    else:
        assert not reranks


@pytest.mark.parametrize("batched", [True, False], ids=["batched", "single"])
def test_cold_counters_match_the_drivers_own_stats(small_dataset,
                                                   small_graph, batched):
    X, Q = small_dataset
    eng = _engine(X, small_graph, len(X) // 4)
    before = eng.snapshot_access_stats()
    res = eng.search(SearchRequest(query=Q[:4] if batched else Q[0],
                                   k=10, ef=32))
    d = _delta(eng, before)
    stats = res.stats if batched else [res.stats]
    n_dist = sum(s.n_dist for s in stats)
    assert d["tier2_misses"] == sum(s.items_fetched for s in stats) > 0
    assert d["tier2_hits"] == n_dist - d["tier2_misses"] > 0
    assert d["host_syncs"] > 0


def test_warm_tier2_serves_every_beam_lookup(small_dataset, small_graph):
    X, Q = small_dataset
    eng = _engine(X, small_graph, len(X))
    eng.warm_cache()
    before = eng.snapshot_access_stats()
    res = eng.search(SearchRequest(query=Q[:4], k=10, ef=32))
    d = _delta(eng, before)
    assert d["tier2_misses"] == 0
    assert d["tier2_hits"] == sum(s.n_dist for s in res.stats) > 0


def test_identical_runs_count_identical_syncs(small_dataset, small_graph):
    X, Q = small_dataset
    deltas = []
    for _ in range(2):
        eng = _engine(X, small_graph, len(X) // 4)
        before = eng.snapshot_access_stats()
        eng.search(SearchRequest(query=Q[:4], k=10, ef=32))
        eng.search(SearchRequest(query=Q[5], k=10, ef=32))
        deltas.append(_delta(eng, before))
    assert deltas[0] == deltas[1]
    assert deltas[0]["host_syncs"] > 0


def test_batched_fill_drops_two_syncs_per_gathered_phase(small_dataset,
                                                        small_graph,
                                                        monkeypatch):
    """The batched staging makes no device->host read, where
    ``gather_batch`` reads the looked-up presence and rows: two per
    gathered phase. The phase program's one miss-list read takes the
    place of the miss-count and miss-id reads: one more."""
    X, Q = small_dataset
    req = SearchRequest(query=Q[:4], k=10, ef=32)
    ref = use_reference_loop(_engine(X, small_graph, len(X) // 4))
    ref.store.fill_batch = lambda ids: jnp.asarray(
        ref.store.gather_batch(ids))
    before = ref.snapshot_access_stats()
    want = ref.search(req)
    want_syncs = _delta(ref, before)["host_syncs"]

    eng = _engine(X, small_graph, len(X) // 4)
    stage = eng.store.stage_batch
    stage_syncs = []

    def counted(ids):
        n0 = eng.external.stats.host_syncs
        out = stage(ids)
        stage_syncs.append(eng.external.stats.host_syncs - n0)
        return out

    def no_read(x, stats):
        raise AssertionError("stage_batch read the device")

    eng.store.stage_batch = counted
    monkeypatch.setattr(store_mod, "to_host", no_read)
    before = eng.snapshot_access_stats()
    got = eng.search(req)
    n_phases = got.batch_stats.n_phases
    assert n_phases == want.batch_stats.n_phases > 0
    assert len(stage_syncs) == n_phases and not any(stage_syncs)
    assert _delta(eng, before)["host_syncs"] == want_syncs - 3 * n_phases


def test_single_fill_drops_two_syncs_per_gathered_phase(small_dataset,
                                                       small_graph,
                                                       monkeypatch):
    """The single-query staging makes no device->host read, where
    ``gather`` reads the looked-up presence and rows: two per gathered
    phase. The phase program's one miss-list read takes the place of
    the miss-count and miss-id reads: one more."""
    X, Q = small_dataset
    req = SearchRequest(query=Q[0], k=10, ef=32)
    ref = use_reference_loop(_engine(X, small_graph, len(X) // 4))

    def gather_fill(ids):
        out = np.zeros((len(ids), X.shape[1]), np.float32)
        out[: (ids >= 0).sum()] = ref.store.gather(ids[ids >= 0])
        return jnp.asarray(out)

    ref.store.fill = gather_fill
    before = ref.snapshot_access_stats()
    want = ref.search(req)
    want_d = _delta(ref, before)

    eng = _engine(X, small_graph, len(X) // 4)
    stage = eng.store.stage
    misses = []

    def counted(ids):
        n0 = eng.external.stats.host_syncs
        out = stage(ids)
        assert eng.external.stats.host_syncs == n0
        misses.append(int((ids >= 0).sum()))
        return out

    def no_read(x, stats):
        raise AssertionError("stage read the device")

    eng.store.stage = counted
    monkeypatch.setattr(store_mod, "to_host", no_read)
    before = eng.snapshot_access_stats()
    got = eng.search(req)
    d = _delta(eng, before)
    n_phases = len(misses)
    assert n_phases == got.stats.n_db == want.stats.n_db > 0
    assert d["host_syncs"] == want_d["host_syncs"] - 3 * n_phases
    assert d["tier2_misses"] == sum(misses) == want_d["tier2_misses"]
    assert d["tier2_hits"] == want_d["tier2_hits"] > 0


@pytest.mark.parametrize("batch", [1, 4], ids=["b1", "b4"])
@pytest.mark.parametrize("eviction", ["fifo", "lru"])
def test_descent_reads_nothing_back(small_dataset, small_graph, eviction,
                                    batch):
    """A search of one query or of a batch reads each phase program's
    miss list once; between layers it reads nothing, and it ends with
    one read of its counts and two of its answer."""
    X, Q = small_dataset
    eng = _engine(X, small_graph, len(X) // 4, eviction=eviction)
    assert small_graph.n_layers > 1
    for q in (Q[:3] if batch == 1 else [Q[i:i + batch] for i in (0, 4, 8)]):
        before = eng.snapshot_access_stats()
        eng.search(SearchRequest(query=q, k=10, ef=32))
        d = _delta(eng, before)
        # each layer's programs: one per gathered phase, plus its entry
        phases = d["n_db"] + small_graph.n_layers
        assert d["host_syncs"] == phases + 3


def test_fused_driver_leaves_the_phase_loop_counters(small_dataset,
                                                     small_graph):
    X, Q = small_dataset
    eng = _engine(X, small_graph, len(X) // 4, fused=True)
    before = eng.snapshot_access_stats()
    eng.search(SearchRequest(query=Q[0], k=10, ef=32))
    d = _delta(eng, before)
    assert d["n_db"] > 0
    assert all(d[key] == 0 for key in COUNTERS)


def test_reset_clears_the_window_counters():
    stats = AccessStats(n_db=3, tier2_hits=5, tier2_misses=7, host_syncs=11)
    stats.reset()
    assert stats == AccessStats()
