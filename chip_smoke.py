#!/usr/bin/env python3
"""Run the engine's served search path once on a TPU and check what comes out.

    python chip_smoke.py [--seed S]                # one chip
    python chip_smoke.py [--seed S] --four-chips   # one host, 4 chips

Both modes build a seeded 768-d corpus and ONE HNSW graph at the paper's
configuration (``configs/webanns.py``: M=16, ef_construction=200, l2) on
the host, and score every result by recall@10 against an exact float32
brute-force top-k.

One chip (the default):

1. ``kernels`` — every Pallas kernel behind ``repro.kernels.ops`` runs
   compiled on the chip at d=768 and is compared with its jnp oracle run
   on the host CPU;
2. three engines serve the same graph through ``WebANNSEngine.search``:
   lazy float32 with a quarter-size tier-2 cache (one batched request
   plus single-query requests, whose ids must agree), fused float32, and
   int8 with its exact rerank.

``--four-chips`` runs only the mesh-sharded driver
(``EngineConfig(n_shards=4)``, one shard per chip) against the warmed
single-device batched driver on the first chip.

Each phase prints one JSON line; any failed check or error exits
non-zero. The last line is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it. Without a TPU the script exits 1 and prints no
result.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

D = 768  # the paper's embedding width (Wiki-480k, configs/webanns.py)
PAPER_N = 480_000
N = 8192  # cut from PAPER_N: see the "reduced" line main() prints
B = 16  # queries in the batched request
K, EF = 10, 64
RECALL_FLOOR = 0.90
SHARDED_ID_AGREEMENT = 0.99  # share of (query, rank) slots
N_SINGLE = 4  # single-query requests checked against the batched ones


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


# ------------------------------------------------------------------ data


def make_corpus(n: int, d: int, n_queries: int, seed: int):
    """Clustered corpus + queries perturbed off corpus points, from seed."""
    from repro.data.synthetic import corpus_embeddings

    X = corpus_embeddings(n, d, seed=seed)
    rng = np.random.default_rng(seed + 1)
    Q = X[rng.choice(n, n_queries, replace=False)]
    Q = Q + 0.35 * rng.standard_normal(Q.shape).astype(np.float32)
    return X, Q.astype(np.float32)


def build_graph(X: np.ndarray, seed: int):
    """(graph, host seconds) at the paper's build configuration."""
    from repro.configs import get
    from repro.core.hnsw import build_hnsw

    cfg = get("webanns").make_config()
    t0 = time.perf_counter()
    g = build_hnsw(X, M=cfg["M"], ef_construction=cfg["ef_construction"],
                   metric=cfg["metric"], seed=seed)
    return g, time.perf_counter() - t0


def exact_l2(X: np.ndarray, Q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 squared distances of X[ids[b]] to Q[b]."""
    x = X[ids].astype(np.float64)
    return ((x - Q[:, None, :].astype(np.float64)) ** 2).sum(-1)


def rel_gap(a, b) -> float:
    """Largest |a - b| relative to the largest |b|."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    fin = np.isfinite(b)
    check(bool((np.isfinite(a) == fin).all()), "same +inf pattern")
    if not fin.any():
        return 0.0
    return float(np.abs(a[fin] - b[fin]).max() / np.abs(b[fin]).max())


# --------------------------------------------------------------- kernels


def kernels_phase(X: np.ndarray, seed: int, batch: int = B,
                  slots: int = 128) -> dict:
    """Each ``ops`` kernel on the default device vs its oracle on the
    host CPU; returns the largest relative gap per kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import quant
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed + 2)
    n, d = X.shape
    Q = X[:batch] + rng.standard_normal((batch, d)).astype(np.float32)
    ids = rng.integers(0, n, (batch, slots)).astype(np.int32)
    ids[:, ::7] = -1  # padding slots
    tab8, sc8 = quant.quantize_np(X, "int8")
    tab16, sc16 = quant.quantize_np(X, "float16")
    m_sub, n_cent = 16, 256
    codes = rng.integers(0, n_cent, (n, m_sub)).astype(np.uint8)
    luts = np.abs(rng.standard_normal((batch, 2, m_sub, n_cent)))
    luts = luts.astype(np.float32)
    cand = 192  # beam (64) + 4 shards x degree-32 candidates
    md = rng.standard_normal((batch, cand)).astype(np.float32) ** 2
    mi = rng.integers(0, cand // 2, (batch, cand)).astype(np.int32)
    mi[rng.random((batch, cand)) < 0.2] = -1
    D_mat = np.stack([((X[:4096] - q) ** 2).sum(-1) for q in Q])

    cos = {"metric": "cos"}
    # (name, tolerance on the relative gap, ops call, oracle, inputs)
    cases = [
        ("distance_matrix", 1e-2, ops.distance_matrix,
         ref.distance_matrix_ref, (Q, X)),
        ("topk", 0.0, lambda D: ops.topk(D, K)[0],
         lambda D: ref.topk_ref(D, K)[0], (D_mat,)),
        ("merge_topk", 0.0, lambda d, i: ops.merge_topk(d, i, EF),
         lambda d, i: ref.merge_topk_ref(d, i, EF), (md, mi)),
        ("gather_distance", 1e-4, ops.gather_distance,
         ref.gather_distance_ref, (X, ids[0], Q[0])),
        ("gather_distance_batch", 1e-4, ops.gather_distance_batch,
         ref.gather_distance_batch_ref, (X, ids, Q)),
        ("gather_distance_batch_cos", 1e-4,
         functools.partial(ops.gather_distance_batch, **cos),
         functools.partial(ref.gather_distance_batch_ref, **cos),
         (X, ids, Q)),
        ("dequant_gather_distance", 1e-4, ops.dequant_gather_distance,
         ref.dequant_gather_distance_ref, (tab8, sc8, ids[0], Q[0])),
        ("dequant_gather_distance_batch", 1e-4,
         ops.dequant_gather_distance_batch,
         ref.dequant_gather_distance_batch_ref, (tab8, sc8, ids, Q)),
        ("dequant_gather_distance_batch_f16", 1e-4,
         ops.dequant_gather_distance_batch,
         ref.dequant_gather_distance_batch_ref, (tab16, sc16, ids, Q)),
        ("adc_gather_distance", 1e-6, ops.adc_gather_distance,
         ref.adc_gather_distance_ref, (codes, luts[0, :1], ids[0])),
        ("adc_gather_distance_batch_cos", 1e-6,
         functools.partial(ops.adc_gather_distance_batch, **cos),
         functools.partial(ref.adc_gather_distance_batch_ref, **cos),
         (codes, luts, ids)),
    ]
    cpu = jax.devices("cpu")[0]
    gaps = {}
    for name, tol, kernel, oracle, inputs in cases:
        got = jax.block_until_ready(kernel(*map(jnp.asarray, inputs)))
        want = oracle(*(jax.device_put(x, cpu) for x in inputs))
        gaps[name] = max(
            rel_gap(a, b)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        check(gaps[name] <= tol, f"{name}: gap {gaps[name]} > {tol}")
    return {"phase": "kernels", "device": jax.devices()[0].platform,
            "max_rel_gap": gaps}


# --------------------------------------------------------------- engines


def search(engine, request):
    """``engine.search(request)`` with its ids and dists ready."""
    import jax

    res = engine.search(request)
    jax.block_until_ready((res.ids, res.dists))
    return res


def engine_configs(n: int):
    """(name, EngineConfig, single-query requests, needs tier-3 fetches)."""
    from repro.core.engine import EngineConfig

    cap = n // 4  # tier 2 holds a quarter of the corpus
    return [
        ("lazy_f32", EngineConfig(cache_capacity=cap), N_SINGLE, True),
        ("fused_f32", EngineConfig(fused=True, cache_capacity=cap), 0,
         False),
        ("int8_rerank", EngineConfig(precision="int8", cache_capacity=cap),
         0, False),
    ]


def serve_phase(name, X, g, Q, truth, config, n_single=0,
                needs_tier3=False) -> dict:
    """One batched request (plus ``n_single`` single-query requests on a
    fresh twin engine) through ``WebANNSEngine.search``."""
    from repro.core.engine import SearchRequest, WebANNSEngine
    from repro.core.eval import recall_at_k

    t0 = time.perf_counter()
    res = search(WebANNSEngine(X, g, config),
                 SearchRequest(query=Q, k=K, ef=EF))
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    out = {
        "phase": name,
        "recall@10": recall_at_k(ids, truth),
        "n_db_per_query": res.batch_stats.n_db_per_query,
        "max_rel_dist_err": rel_gap(dists, exact_l2(X, Q, ids)),
    }
    check(out["recall@10"] >= RECALL_FLOOR,
          f"{name}: recall@10 {out['recall@10']} < {RECALL_FLOOR}")
    if needs_tier3:
        check(res.batch_stats.n_db > 0, f"{name}: no tier-3 fetch")
    if n_single:
        twin = WebANNSEngine(X, g, config)
        single = [search(twin, SearchRequest(query=q, k=K, ef=EF))
                  for q in Q[:n_single]]
        out["single_ids_agree"] = all(
            set(np.asarray(s.ids).tolist()) == set(ids[b].tolist())
            for b, s in enumerate(single))
        out["single_ids_same_order"] = all(
            np.array_equal(s.ids, ids[b]) for b, s in enumerate(single))
        out["single_max_rel_dist_gap"] = max(
            rel_gap(s.dists, dists[b]) for b, s in enumerate(single))
        check(out["single_ids_agree"], f"{name}: batched vs single ids")
    out["phase_s"] = time.perf_counter() - t0  # compilation included
    return out


def sharded_phase(X, g, Q, truth, n_shards: int) -> dict:
    """Mesh-sharded driver vs the warmed single-device batched driver."""
    import jax

    from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
    from repro.core.eval import recall_at_k

    req = SearchRequest(query=Q, k=K, ef=EF)
    t0 = time.perf_counter()
    ref_eng = WebANNSEngine(X, g, EngineConfig())
    ref_eng.warm_cache()
    want = search(ref_eng, req)
    t1 = time.perf_counter()
    eng = WebANNSEngine(X, g, EngineConfig(n_shards=n_shards))
    got = search(eng, req)
    t2 = time.perf_counter()
    mesh, st = eng._shard_runtime()
    shards = st.table.addressable_shards
    placed = {str(s.device) for s in shards}
    check(len(set(mesh.devices.flat)) == n_shards,
          f"mesh spans {len(set(mesh.devices.flat))} devices")
    check(len(shards) == n_shards and len(placed) == n_shards
          and all(s.data.shape[0] == 1 for s in shards),
          f"table rows spread as {[s.data.shape for s in shards]}")
    ids, wids = np.asarray(got.ids), np.asarray(want.ids)
    out = {
        "phase": f"sharded_{n_shards}",
        "devices": sorted(placed),
        "rows_per_shard": int(shards[0].data.shape[1]),
        "recall@10": recall_at_k(ids, truth),
        "ref_recall@10": recall_at_k(wids, truth),
        "id_agreement": float(np.mean(ids == wids)),
        "max_rel_dist_gap": rel_gap(got.dists, want.dists),
        "ref_phase_s": t1 - t0,
        "sharded_phase_s": t2 - t1,  # compilation included
        "default_device": str(jax.devices()[0]),
    }
    for key in ("recall@10", "ref_recall@10"):
        check(out[key] >= RECALL_FLOOR, f"{key} {out[key]}")
    check(out["id_agreement"] >= SHARDED_ID_AGREEMENT,
          f"id agreement {out['id_agreement']}")
    return out


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (default device "
              f"{devs[0].platform}); nothing was run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # before the first compile
    n_chips = 4 if args.four_chips else 1  # the chips this mode runs on
    check(len(devs) >= n_chips,
          f"{n_chips} chips needed, {len(devs)} visible")

    emit(phase="setup", config="webanns", n=N, d=D, k=K, ef=EF,
         batch=B, seed=args.seed, compile_cache=cache_dir,
         reduced={"n": {"paper": PAPER_N, "here": N,
                        "why": "the HNSW graph is built on the host one "
                               "point at a time (core/hnsw.py); the "
                               "paper's corpus would not build within "
                               "one run"}})
    X, Q = make_corpus(N, D, B, args.seed)
    g, build_s = build_graph(X, args.seed)
    emit(phase="build", host_build_s=build_s, n_layers=g.n_layers,
         max_degree=g.max_degree)
    from repro.core.eval import brute_force_topk

    truth = brute_force_topk(X, Q, K)
    if args.four_chips:
        emit(**sharded_phase(X, g, Q, truth, n_chips))
    else:
        emit(**kernels_phase(X, args.seed))
        for name, cfg, n_single, tier3 in engine_configs(N):
            emit(**serve_phase(name, X, g, Q, truth, cfg, n_single, tier3))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": n_chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
