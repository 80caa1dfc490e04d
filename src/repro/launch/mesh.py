"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the pod axis
composes with data for every DP-style rule (distributed/sharding.py), so
the same programs scale to N pods by widening DP.

``make_production_mesh`` is a FUNCTION (not module state) so importing
this module never touches jax device state — the dry-run sets its
XLA_FLAGS before first jax init.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices exist —
    used by tests and examples on the CPU container."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_shard_mesh(n_shards: int):
    """1-D ("shard",) mesh over the first ``n_shards`` devices, one shard
    per device — the ANNS index-sharding mesh (DESIGN.md §10)."""
    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"n_shards={n_shards} but only {len(devs)} devices visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "to simulate a mesh on CPU)"
        )
    return jax.make_mesh(
        (n_shards,), ("shard",), devices=devs[:n_shards],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


# TPU v5e hardware constants (roofline §EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
