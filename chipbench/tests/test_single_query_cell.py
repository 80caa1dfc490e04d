"""The one-query-per-request cell builds no program in its window, and the
tiered store's readers read its counters."""

import json
import time

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench import trace as tr
from chipbench.tests.conftest import tiny_cell


def test_cos_b1_window_builds_no_program(capsys):
    cell = tiny_cell("cos", 1)
    out = harness.run_cell(cell, seed=2**31 + 9, seconds=60.0, traced=False,
                           t_start=time.perf_counter(), max_requests=24)
    assert out["correct"], out["checks"]
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    (window,) = [line for line in lines if line.get("phase") == "window"]
    assert window["requests"] == 24
    assert window["tier3_accesses"] > 0  # the phase loop went to tier 3
    assert window["compiles_in_window"] == 0
    assert window["cache_loads_in_window"] == 0


def _run(n_db, items_fetched, window_s=2.0, traced=True):
    summary = tr.TraceSummary(window_s=window_s, busy_s=0.5, launches=40,
                              n_requests=10, device_ops=[], idle_gaps=[]) \
        if traced else None
    return harness.Run(
        config={}, setup_s=1.0, window_s=window_s + 0.1,
        latencies_s=np.full(10, 0.2), n_queries=10, recall=1.0,
        counters={"n_db": n_db, "items_fetched": items_fetched,
                  "n_dist": 1000},
        trace=summary, peaks=None)


@pytest.mark.parametrize("name, want", [
    ("tier3_fetches_per_query", 8.0), ("tier3_rows_per_query", 400.0)])
def test_tier3_readers_read_a_one_query_window(name, want):
    """The tiered store's readers, scoped to this cell too, read its
    counters traced or not, and nothing where tier 3 was not touched."""
    read = spec.reader(name)
    assert read(_run(n_db=80, items_fetched=4000)) == pytest.approx(want)
    assert read(_run(n_db=80, items_fetched=4000, traced=False)) \
        == pytest.approx(want)
    assert read(_run(n_db=0, items_fetched=0)) is None
