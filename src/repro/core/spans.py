"""Host spans of the search drivers and the tiered store (DESIGN.md §5).

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the same
trace as the device's programs when a profiler runs, and costs about
half a microsecond when none does, so it needs no switch. A span's
parent is the span that contains it on the calling thread; every span
of one request lies inside that request's ``search`` span.
"""

from __future__ import annotations

import jax
import numpy as np

# every span the program writes, in the order a request meets them
SPAN_NAMES = (
    "search",  # WebANNSEngine.search: the whole call, every driver
    "seed",  # layer entry: seed-and-first-phase dispatch, miss-list read
    "tier2_gather",  # TieredStore.stage / stage_batch: union, fetch, pack
    "tier3_fetch",  # ExternalStore.fetch: the backend read
    "beam_phase",  # fill-load-phase dispatch and its miss-list read
    "descend",  # between layers: the entry update (on the device)
    "finalize",  # the final top-k read
    "rerank",  # exact rerank of a quantized search (one tier-3 access)
)


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name)


def to_host(x, stats) -> np.ndarray:
    """``np.asarray(x)``, a blocking device->host read, counted in
    ``stats.host_syncs`` (an :class:`~repro.core.store.AccessStats`)."""
    stats.host_syncs += 1
    return np.asarray(x)
