"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself only runs on a TPU; here its phase functions run at
N=600, d=32 with the same checks (recall floor, tier-3 fetches in the
lazy engine, batched-vs-single id agreement), its kernel phase runs the
interpret-mode kernels, and its four-chip phase runs on four virtual
CPU devices in a subprocess.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.eval import brute_force_topk

ROOT = Path(__file__).resolve().parents[1]
N, DIM, NQ = 600, 32, 16


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()
ENGINES = {c[0]: c for c in smoke.engine_configs(N)}


@pytest.fixture(scope="module")
def corpus():
    X, Q = smoke.make_corpus(N, DIM, NQ, seed=0)
    g, _ = smoke.build_graph(X, seed=0)
    return X, Q, g, brute_force_topk(X, Q, smoke.K)


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_serve_phase(corpus, name):
    X, Q, g, truth = corpus
    _, cfg, n_single, needs_tier3 = ENGINES[name]
    out = smoke.serve_phase(name, X, g, Q, truth, cfg, n_single,
                            needs_tier3)
    assert out["recall@10"] >= smoke.RECALL_FLOOR
    assert out["max_rel_dist_err"] < 1e-5
    if needs_tier3:
        assert out["n_db_per_query"] > 0
    if n_single:
        assert out["single_ids_agree"]


def test_kernels_phase_interpret(corpus, monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    out = smoke.kernels_phase(corpus[0], seed=0, batch=3, slots=20)
    assert len(out["max_rel_gap"]) == 11


def test_script_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, env=env, timeout=300, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


FOUR_CHIPS = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke as smoke
from repro.core.eval import brute_force_topk
X, Q = smoke.make_corpus(%d, %d, %d, seed=0)
g, _ = smoke.build_graph(X, seed=0)
out = smoke.sharded_phase(X, g, Q, brute_force_topk(X, Q, smoke.K), 4)
print("RESULT:" + json.dumps(out))
""" % (N, DIM, NQ)


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(ROOT)], capture_output=True,
        text=True, env=env, timeout=600, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")]
    out = json.loads(line[0][len("RESULT:"):])
    assert len(out["devices"]) == 4
    # on the CPU both drivers run the jnp reference: identical results
    assert out["id_agreement"] == 1.0
    assert out["max_rel_dist_gap"] == 0.0
    assert out["recall@10"] >= smoke.RECALL_FLOOR
