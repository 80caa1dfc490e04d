"""Every hot-path Pallas kernel compiles for a TPU v5e chip, at d=768.

Interpret mode never checks TPU tiling, so the kernel sweeps in
test_kernels.py / test_quant.py / test_pq.py / test_merge_topk.py cannot
see a block shape or an op the chip's compiler refuses. These tests
compile each ``kernels/ops.py`` kernel with ``interpret=False`` for one
chip of a *described* ``v5e:2x2`` topology — no TPU needed — and check
that the kernel is really in the program (``tpu_custom_call``).

Shapes are the paper's configuration (configs/webanns.py): 768-d rows,
a 100k-row table, a batch of 64 queries with 128 ids each, k=10.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adc_gather_distance import (
    adc_gather_distance_batch_pallas,
    adc_gather_distance_pallas,
)
from repro.kernels.dequant_gather_distance import (
    dequant_gather_distance_batch_pallas,
    dequant_gather_distance_pallas,
)
from repro.kernels.distance import distance_matrix_pallas
from repro.kernels.gather_distance import (
    gather_distance_batch_pallas,
    gather_distance_pallas,
)
from repro.kernels.topk import merge_topk_pallas, topk_pallas

N, D, B, K, TOPK = 100_000, 768, 64, 128, 10
PQ_M, PQ_C = 16, 256
MERGE_M, EF = 64 + 4 * 32, 64  # beam + 4 shards × degree-32 candidates

f32, i32 = jnp.float32, jnp.int32

# name -> (kernel, static kwargs, [(shape, dtype), ...])
CASES = {
    "distance_matrix": (distance_matrix_pallas, {},
                        [((B, D), f32), ((N, D), f32)]),
    "topk": (topk_pallas, {"k": TOPK}, [((B, N), f32)]),
    "merge_topk": (merge_topk_pallas, {"k": EF},
                   [((B, MERGE_M), f32), ((B, MERGE_M), i32)]),
    "gather_distance": (gather_distance_pallas, {},
                        [((N, D), f32), ((K,), i32), ((D,), f32)]),
    "gather_distance_batch": (gather_distance_batch_pallas, {},
                              [((N, D), f32), ((B, K), i32), ((B, D), f32)]),
    "dequant_gather_distance": (
        dequant_gather_distance_pallas, {},
        [((N, D), jnp.int8), ((N,), f32), ((K,), i32), ((D,), f32)]),
    "dequant_gather_distance_batch": (
        dequant_gather_distance_batch_pallas, {},
        [((N, D), jnp.int8), ((N,), f32), ((B, K), i32), ((B, D), f32)]),
    "adc_gather_distance": (
        adc_gather_distance_pallas, {},
        [((N, PQ_M), jnp.uint8), ((1, PQ_M, PQ_C), f32), ((K,), i32)]),
    "adc_gather_distance_batch": (
        adc_gather_distance_batch_pallas, {},
        [((N, PQ_M), jnp.uint8), ((B, 1, PQ_M, PQ_C), f32), ((B, K), i32)]),
    # cos carries a second LUT plane: twice the kernel's VMEM
    "adc_gather_distance_batch_cos": (
        adc_gather_distance_batch_pallas, {"metric": "cos"},
        [((N, PQ_M), jnp.uint8), ((B, 2, PQ_M, PQ_C), f32), ((B, K), i32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the persistent compile
    cache off (a described-topology compile can be written to the cache
    but never read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, static, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(
        lambda *a: kernel(*a, interpret=False, **static)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
