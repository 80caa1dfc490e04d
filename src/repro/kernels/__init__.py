"""Pallas TPU kernels for the compute hot path (+ jnp oracles).

Kernels (each <name>.py has the pl.pallas_call; ops.py wraps; ref.py is
the pure-jnp oracle):

- distance.py        blocked (B,N) distance matrix in MXU matmul form
- topk.py            split-K partial top-k (FlashDecoding-style)
- gather_distance.py fused scalar-prefetch gather + distance (ANNS hot path)
- dequant_gather_distance.py
                     the quantized twin: int8/f16 rows + per-row scales
                     dequantized in-kernel, ~4x less HBM held (§7)
- embedding_bag.py   fused gather-accumulate embedding bag (recsys)
"""

from repro.kernels import ops  # noqa: F401
