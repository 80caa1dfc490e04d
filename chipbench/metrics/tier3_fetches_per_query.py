"""Tier-3 accesses per query over the window: the engine's ``n_db``
counter (one per load phase with a miss, for the whole batch)."""


def read(run):
    n_db = run.counters.get("n_db")
    if not n_db:
        return None
    return n_db / run.n_queries
