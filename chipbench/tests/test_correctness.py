"""``correct`` holds for the engine and fails for the control and for each
fault a cell can have, at a small size on the CPU."""

import dataclasses
import time

import numpy as np
import pytest

from chipbench import control, harness
from chipbench.tests.conftest import tiny_cell

REQUESTS = 6  # per run: enough to cover several passes of the phase loop


def run(cell, make_engine=harness.make_engine):
    return harness.run_cell(cell, seed=2**31 + 5, seconds=60.0,
                            traced=False, t_start=time.perf_counter(),
                            make_engine=make_engine, max_requests=REQUESTS)


def test_engine_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == REQUESTS * cell.traffic["batch"]
    assert out["checks"]["dist_gap"]["value"] < 1e-6
    assert list(out)[-1] == "checks"


def test_bf16_control_is_refused(cell):
    out = run(cell, control.bf16_brute_force)
    assert not out["correct"]
    gap = out["checks"]["dist_gap"]
    assert gap["value"] > gap["limit"]


class _Faulty:
    """The engine with its answers broken where they are produced."""

    def __init__(self, engine, fault):
        self.engine, self.fault = engine, fault

    def __getattr__(self, name):  # the engine's other calls pass through
        return getattr(self.engine, name)

    def search(self, request):
        res = self.engine.search(request)
        ids = np.array(res.ids)
        dists = np.array(res.dists)
        if self.fault == "altered_id":
            ids.reshape(-1, ids.shape[-1])[:, 0] += 1
        elif self.fault == "half_batch":  # the rest answered as the first
            half = len(ids) // 2
            ids[half:], dists[half:] = ids[:half], dists[:half]
        return dataclasses.replace(res, ids=ids, dists=dists)


@pytest.mark.parametrize("metric,batch,fault", [
    ("l2", 4, "altered_id"), ("l2", 4, "half_batch"),
    ("cos", 1, "altered_id"),  # a one-query request has no half
])
def test_fault_is_refused(metric, batch, fault):
    def make(*args):
        return _Faulty(harness.make_engine(*args), fault)

    assert not run(tiny_cell(metric, batch), make)["correct"]
