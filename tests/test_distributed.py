"""Device-sharded search on a simulated multi-device mesh.

Engine-facing: exercises ``EngineConfig(n_shards=S)`` — the shard_map
beam phase + fused cross-shard top-k merge (DESIGN.md §10) — against the
single-device batched driver, asserting BIT-equality of ids and dists
(not recall). One smoke test keeps the legacy flat-scan substrate alive
(``launch/dryrun.py`` still drives it).

Runs in a subprocess so XLA_FLAGS (device count) never leaks into the
main test process (smoke tests must see 1 device).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dshard
from repro.core.engine import EngineConfig, SearchRequest, WebANNSEngine
from repro.core.metadata import Filter

rng = np.random.default_rng(0)
N, d, B, k = 1200, 24, 8, 10
X = rng.standard_normal((N, d)).astype(np.float32)
Q = rng.standard_normal((B, d)).astype(np.float32)
meta = {"cat": (np.arange(N) % 4).astype(np.int64)}
dead = np.arange(0, N, 11)
filt = Filter.in_("cat", [0, 2])

def results(engine, warm=False):
    # warm=True for the single-device reference: the sharded engine's
    # per-shard slab is 100% resident, so its bitwise twin is the WARM
    # lazy driver (cold expansion order is cache-state-dependent)
    if warm:
        engine.warm_cache()
    plain = engine.search(SearchRequest(query=Q, k=k))
    filtered = engine.search(SearchRequest(query=Q, k=k, filter=filt))
    engine.delete(dead)
    if warm:
        engine.warm_cache()
    tombed = engine.search(SearchRequest(query=Q, k=k))
    return plain, filtered, tombed

def pack(r):
    return [np.asarray(r.ids), np.asarray(r.dists)]

ref = WebANNSEngine.build(X, M=8, ef_construction=60, seed=3,
                          metadata=dict(meta))
want = [pack(r) for r in results(ref, warm=True)]

out = {"n_devices": len(jax.devices())}
for S in (2, 4, 8):
    eng = WebANNSEngine.build(X, M=8, ef_construction=60, seed=3,
                              metadata=dict(meta),
                              config=EngineConfig(n_shards=S))
    got = [pack(r) for r in results(eng)]
    for name, w, g in zip(("plain", "filtered", "tombstoned"), want, got):
        out[f"S{S}_{name}_ids"] = bool(np.array_equal(w[0], g[0]))
        out[f"S{S}_{name}_dists"] = bool(np.array_equal(w[1], g[1]))

# int8: sharded table is fully resident (dequantized per shard) — warm
# the reference so its tier-2 cache serves the same dequantized payload
ref8 = WebANNSEngine.build(X, M=8, ef_construction=60, seed=3,
                           config=EngineConfig(precision="int8"))
ref8.warm_cache()
w8 = pack(ref8.search(SearchRequest(query=Q, k=k)))
eng8 = WebANNSEngine.build(X, M=8, ef_construction=60, seed=3,
                           config=EngineConfig(precision="int8", n_shards=8))
g8 = pack(eng8.search(SearchRequest(query=Q, k=k)))
out["S8_int8_ids"] = bool(np.array_equal(w8[0], g8[0]))
out["S8_int8_dists"] = bool(np.array_equal(w8[1], g8[1]))

# collectives actually lowered: the layer-0 program must contain an
# all-gather (candidate exchange) for the fused cross-shard merge
eng = WebANNSEngine.build(X, M=8, ef_construction=60, seed=3,
                          config=EngineConfig(n_shards=8))
eng.search(SearchRequest(query=Q, k=k))
mesh, st = eng._shard_runtime()
prog = dshard.sharded_layer_program(mesh, 64, "l2", False)
lowered = prog.lower(
    jnp.asarray(Q), jnp.zeros((B, 1), jnp.int32), st.table, st.scales,
    st.neighbors[:, 0], st.tombstones,
)
hlo = lowered.compile().as_text()
out["has_allgather"] = "all-gather" in hlo

# per-shard fetches stay shard-local: building the device state reads
# each backend range exactly once, no cross-shard gathers on the host
from repro.core.storage import mesh_shard_ranges
ranges = mesh_shard_ranges(N, 8)
out["ranges_cover"] = bool(
    ranges[0][0] == 0 and ranges[-1][1] == N
    and all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
)

# legacy flat-scan substrate smoke (dryrun path)
from repro.core.distributed import build_sharded_index, distributed_brute_force
from repro.core.hnsw import exact_search
mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
idx = build_sharded_index(X, 4, M=8, ef_construction=60)
with mesh2:
    fd, fi = distributed_brute_force(mesh2, k=k)(jnp.asarray(Q), idx)
hits = sum(
    len(set(np.asarray(fi[b]).tolist())
        & set(exact_search(X, Q[b], k)[0].tolist()))
    for b in range(B)
)
out["recall_flat"] = hits / (k * B)
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULT:"):])


def test_runs_on_eight_devices(dist_result):
    assert dist_result["n_devices"] == 8


@pytest.mark.parametrize("S", [2, 4, 8])
@pytest.mark.parametrize("variant", ["plain", "filtered", "tombstoned"])
def test_sharded_bit_parity(dist_result, S, variant):
    assert dist_result[f"S{S}_{variant}_ids"], f"S={S} {variant}: ids"
    assert dist_result[f"S{S}_{variant}_dists"], f"S={S} {variant}: dists"


def test_sharded_int8_bit_parity(dist_result):
    assert dist_result["S8_int8_ids"]
    assert dist_result["S8_int8_dists"]


def test_sharded_layer_uses_collectives(dist_result):
    assert dist_result["has_allgather"]


def test_shard_ranges_partition(dist_result):
    assert dist_result["ranges_cover"]


def test_legacy_flat_scan_exact(dist_result):
    assert dist_result["recall_flat"] == 1.0
