"""One run of one cell: set-up, a closed-loop window, the comparison.

The window drives ``WebANNSEngine.search(SearchRequest(query, k, ef))``
from one client that waits for each reply, as the engine's in-process
callers do. Every answer of the window is compared, once the window has
closed, with the exact reference (:mod:`chipbench.compare`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Callable, Iterator, List, Optional

import numpy as np

from chipbench import compare, data, spec
from chipbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, ".trace")
SPANS = ("request", "tier3_fetch")
# A traced run's window: long enough for dozens of requests of the
# slowest cell, short enough that the trace is written and read well
# inside a run's time limit.
TRACE_SECONDS = 10.0


class CompileCounter:
    """Counts the programs this process builds, from JAX's monitoring
    events: ``n`` compiled or loaded, ``loads`` of them found in the
    persistent compilation cache."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_load)

    def _on_build(self, name, _secs, **_kw):
        if name == self.BUILD:
            self.n += 1

    def _on_load(self, name, **_kw):
        if name == self.LOAD:
            self.loads += 1


class SpannedBackend:
    """Tier 3 in host RAM, each fetch inside a ``tier3_fetch`` span.
    Used only in traced runs; the untraced runs give the engine the plain
    array, which it wraps in its own host-RAM backend."""

    def __init__(self, vectors: np.ndarray):
        self._vectors = vectors

    @property
    def n_items(self) -> int:
        return int(self._vectors.shape[0])

    @property
    def dim(self) -> int:
        return int(self._vectors.shape[1])

    @property
    def vectors(self) -> np.ndarray:
        return self._vectors

    def fetch(self, ids: np.ndarray) -> np.ndarray:
        import jax

        with jax.profiler.TraceAnnotation("tier3_fetch"):
            return self._vectors[np.asarray(ids)]

    def access_cost(self, n: int) -> float:
        return 0.0


@dataclasses.dataclass
class Run:
    """What a metric reader (``metrics/<name>.py``) is given."""

    config: dict
    setup_s: float
    window_s: float  # host clock, first request sent to last answered
    latencies_s: np.ndarray  # one per request
    n_queries: int  # answered in the window
    recall: float  # mean recall@k of every answered query
    counters: dict  # program counters over the window; None if absent
    trace: Optional[tr.TraceSummary]  # traced runs only
    peaks: Optional[dict]  # the device's row of peaks.json


def build_graph(X, config: dict, seed: int):
    """The program's own HNSW build at the configuration's settings."""
    from repro.core.hnsw import build_hnsw

    return build_hnsw(X, M=config["M"],
                      ef_construction=config["ef_construction"],
                      metric=config["metric"], seed=seed)


def make_engine(X, graph, config: dict, capacity: int, traced: bool):
    from repro.core.engine import EngineConfig, WebANNSEngine

    source = SpannedBackend(X) if traced else X
    return WebANNSEngine(source, graph, EngineConfig(
        cache_capacity=capacity, **config["engine"]))


def request_stream(pool: int, batch: int, seed: int) -> Iterator[np.ndarray]:
    """Pool indices of successive requests: seeded passes over the pool."""
    rng = np.random.default_rng([seed, 1])
    while True:
        perm = rng.permutation(pool)
        for lo in range(0, pool - batch + 1, batch):
            yield perm[lo:lo + batch]


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, *, make_engine: Callable = make_engine,
             counter: Optional[CompileCounter] = None,
             max_requests: Optional[int] = None) -> dict:
    """Run ``cell`` once; returns the result line (a dict). Set-up makes
    the corpus from ``seed`` and builds its index graph, the same work in
    every run."""
    import jax
    from repro.core.engine import SearchRequest

    counter = counter or CompileCounter()
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    cfg, trf = cell.config, cell.traffic
    B, k = trf["batch"], cfg["k"]
    X, pool = data.make_data(cfg["generator"], cfg["n"], cfg["dim"],
                             trf["query_pool"], seed)
    t_graph = time.perf_counter()
    graph = build_graph(X, cfg, seed)
    emit(phase="graph", seconds=time.perf_counter() - t_graph,
         n_layers=graph.n_layers)
    capacity = max(1, round(cfg["n"] * trf["tier2_fraction"]))
    engine = make_engine(X, graph, cfg, capacity, traced)
    if trf["warm_tier2"]:
        engine.warm_cache()
    stream = request_stream(trf["query_pool"], B, seed)

    def one(idx):
        q = pool[idx] if B > 1 else pool[idx[0]]
        t0 = time.perf_counter()
        res = engine.search(SearchRequest(query=q, k=k, ef=cfg["ef"]))
        jax.block_until_ready((res.ids, res.dists))
        return res, time.perf_counter() - t0

    # warm-up: at least `warmup_requests`, ending with half as many in a
    # row that compiled nothing, so every shape of this traffic is built
    n_warm = trf["warmup_requests"]
    done = quiet = 0
    while (done < n_warm or quiet < max(1, n_warm // 2)) \
            and done < 4 * n_warm:
        c0 = counter.n
        one(next(stream))
        done += 1
        quiet = quiet + 1 if counter.n == c0 else 0
    emit(phase="warmup", requests=done, programs_built=counter.n,
         of_them_cache_loads=counter.loads)

    before = engine.snapshot_access_stats()
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the benchmark's spans, not every call
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    span = (lambda: jax.profiler.TraceAnnotation("request")) if traced \
        else contextlib.nullcontext
    answers: List[tuple] = []
    lat: List[float] = []
    n_dist = 0
    c0, l0 = counter.n, counter.loads
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        idx = next(stream)
        with span():
            res, dt = one(idx)
        lat.append(dt)
        answers.append((idx, res.ids, res.dists))
        stats = res.stats if isinstance(res.stats, list) else [res.stats]
        n_dist += sum(s.n_dist for s in stats)
        if time.perf_counter() - t0 >= seconds or (
                max_requests is not None and len(lat) >= max_requests):
            break
    window_s = time.perf_counter() - t0
    loads = counter.loads - l0
    compiles = counter.n - c0 - loads
    if traced:
        jax.profiler.stop_trace()
    after = engine.snapshot_access_stats()

    devices = jax.devices()[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    counters = {key: after[key] - before[key]
                for key in ("n_db", "items_fetched")}
    counters["n_dist"] = n_dist
    tier2_bytes = engine.cache_bytes()
    n_queries = sum(len(a[0]) for a in answers)
    emit(phase="window", requests=len(lat), queries=n_queries,
         window_s=window_s, latency_samples=len(lat),
         compiles_in_window=compiles, cache_loads_in_window=loads,
         memory_peak_bytes=peak,
         tier2_bytes=tier2_bytes, tier3_accesses=counters["n_db"],
         tier3_rows=counters["items_fetched"], n_dist=n_dist)
    del engine, res
    gc.collect()

    summary = None
    if traced:
        summary = tr.summarize_dir(TRACE_DIR, SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    t_ref = time.perf_counter()
    checks, recall = compare.compare(X, pool, answers, cfg,
                                     tier2_bytes, capacity)
    emit(phase="reference", seconds=time.perf_counter() - t_ref)

    kind = devices[0].device_kind
    run = Run(config=cfg, setup_s=setup_s, window_s=window_s,
              latencies_s=np.asarray(lat), n_queries=n_queries,
              recall=recall, counters=counters, trace=summary,
              peaks=spec.peaks(kind) if devices[0].platform == "tpu"
              else None)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": n_queries,
           "failed": checks["bad_answers"]["value"],
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {name: {"value": c["value"], "limit": c["limit"]}
                     for name, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    return out
