"""The harness finds its files by name, refuses to run without a TPU, and
its data, reference and peak table behave."""

import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import data, reference, spec

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell_name):
    cell = spec.resolve(BENCH, cell_name)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "qps"}
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))
    for key in ("n", "dim", "metric", "M", "ef_construction", "ef", "k"):
        assert key in cell.config
    assert cell.config["engine"]["metric"] == cell.config["metric"]
    for key in ("batch", "tier2_fraction", "warm_tier2", "query_pool",
                "warmup_requests"):
        assert key in cell.traffic


def test_every_config_file_is_used():
    files = {c["file"] for c in BENCH["configs"]}
    used = {f"chipbench/configs/{w['config']}.json"
            for w in BENCH["workloads"]}
    assert files == used


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=spec.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "TPU" in proc.stderr


def test_reference_matches_direct_float64():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((60, 7)).astype(np.float32)
    Q = rng.standard_normal((5, 7)).astype(np.float32)
    for metric in ("l2", "cos"):
        ids, dists = reference.exact_topk(X, Q, 4, metric)
        for b, q in enumerate(Q.astype(np.float64)):
            x = X.astype(np.float64)
            if metric == "l2":
                d = [float(((row - q) ** 2).sum()) for row in x]
            else:
                d = [float(-(row @ q) / (np.linalg.norm(row)
                                         * np.linalg.norm(q))) for row in x]
            want = np.argsort(d, kind="stable")[:4]
            np.testing.assert_array_equal(ids[b], want)
            np.testing.assert_allclose(dists[b], np.asarray(d)[want],
                                       rtol=1e-12)


@pytest.mark.parametrize("queries", ["perturbed", "fresh"])
def test_data_follow_the_seed(queries):
    gen = {"clusters": 4, "spread": 0.35, "queries": queries,
           "query_noise": 0.35}
    X1, Q1 = data.make_data(gen, 50, 6, 8, 2**31 + 11)
    X2, Q2 = data.make_data(gen, 50, 6, 8, 2**31 + 12)
    X3, Q3 = data.make_data(gen, 50, 6, 8, 2**31 + 11)
    np.testing.assert_array_equal(X1, X3)  # the same seed, the same data
    np.testing.assert_array_equal(Q1, Q3)
    assert not np.array_equal(X1, X2)
    assert not np.array_equal(Q1, Q2)


def test_peak_table_refuses_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
