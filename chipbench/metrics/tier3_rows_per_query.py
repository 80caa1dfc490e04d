"""Rows read from tier 3 per query over the window: the engine's
``items_fetched`` counter (deduplicated across a batch)."""


def read(run):
    rows = run.counters.get("items_fetched")
    if not rows:
        return None
    return rows / run.n_queries
