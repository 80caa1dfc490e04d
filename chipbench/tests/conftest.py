"""Tiny cells for the benchmark's CPU tests."""

import pytest

from chipbench import spec


def tiny_cell(metric: str, batch: int, resident: bool = False) -> spec.Cell:
    config = {
        "name": f"tiny-{metric}", "n": 400, "dim": 16, "metric": metric,
        "M": 8, "ef_construction": 40, "ef": 32, "k": 10,
        "generator": {"clusters": 8, "spread": 0.35, "queries":
                      "perturbed" if metric == "l2" else "fresh",
                      "query_noise": 0.35},
        "engine": {"metric": metric, "ef_search": 32, "fused": False,
                   "precision": "float32", "eviction": "fifo",
                   "simulate_latency": False},
        "guarantees": {"recall_at_10_min": 0.9},
    }
    traffic = {"batch": batch, "tier2_fraction": 1.0 if resident else 0.25,
               "warm_tier2": resident, "query_pool": 64, "warmup_requests": 2}
    return spec.Cell(name=f"tiny-{metric}-b{batch}{'-res' if resident else ''}",
                     chips=1, config=config,
                     traffic=traffic, end_to_end=[], per_layer=[])


@pytest.fixture(params=[("l2", 4), ("cos", 1), ("l2", 4, True)],
                ids=["l2-b4", "cos-b1", "l2-b4-resident"])
def cell(request):
    return tiny_cell(*request.param)
