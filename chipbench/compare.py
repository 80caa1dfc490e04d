"""The comparison that decides ``correct``.

Every answer of the window is held against the exact reference
(:mod:`chipbench.reference`) at the timed sizes:

- ``dist_gap``: the largest gap between a returned distance and the
  exact float64 distance of the returned id, relative to the largest
  exact distance of that answer. A row fetched wrongly from tier 3, a
  distance computed in a lower precision, or an id altered after its
  distance was computed all show here.
- ``recall_at_10``: mean recall of every answer against the exact top-k,
  held to the floor that the configuration states.
- ``bad_answers``: answers that are malformed (wrong shape, an id out of
  range or repeated, a distance that is not finite); limit 0.
- ``tier2_bytes``: the tier-2 slab, held to the cell's capacity at
  float32 rows.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from chipbench import reference

# Set from readings on the chip (PERF.md, "How correct is decided"):
# sound runs of the program read at most 2.6e-7 (float32 rounding), the
# bfloat16 control at least 2.5e-2 (wiki768-l2, B=16).
DIST_GAP_LIMIT = 1e-4
BLOCK = 1024  # answers per block of the float64 distance check


def _rows(answers: List[tuple], k: int):
    """Stack (pool index, ids, dists) of every answer; malformed answers
    get ids -1 so they are counted as bad."""
    idx, ids, dists = [], [], []
    for q_idx, a_ids, a_dists in answers:
        b = len(q_idx)
        a_ids = np.asarray(a_ids).reshape(b, -1) if np.size(a_ids) == b * k \
            else np.full((b, k), -1)
        a_dists = np.asarray(a_dists, np.float64)
        a_dists = a_dists.reshape(b, k) if a_dists.size == b * k \
            else np.full((b, k), np.nan)
        idx.append(np.asarray(q_idx))
        ids.append(a_ids.astype(np.int64))
        dists.append(a_dists)
    return np.concatenate(idx), np.concatenate(ids), np.concatenate(dists)


def compare(X: np.ndarray, pool: np.ndarray, answers: List[tuple],
            config: dict, tier2_bytes: int, capacity: int) -> Tuple[dict, float]:
    """(checks, mean recall) of every answer against the reference."""
    k, metric = config["k"], config["metric"]
    idx, ids, dists = _rows(answers, k)
    in_range = ((ids >= 0) & (ids < len(X))).all(1)
    s = np.sort(ids, axis=1)
    distinct = (s[:, 1:] != s[:, :-1]).all(1)
    good = in_range & distinct & np.isfinite(dists).all(1)

    gap = 0.0
    g_idx, g_ids, g_dists = idx[good], ids[good], dists[good]
    for lo in range(0, len(g_idx), BLOCK):
        sl = slice(lo, lo + BLOCK)
        exact = reference.pair_distances(X, pool[g_idx[sl]], g_ids[sl],
                                         metric)
        scale = np.maximum(np.abs(exact).max(1), 1e-30)
        gap = max(gap, float((np.abs(g_dists[sl] - exact).max(1)
                              / scale).max(initial=0.0)))

    uniq, inv = np.unique(idx, return_inverse=True)
    top, _ = reference.exact_topk(X, pool[uniq], k, metric)
    hits = (ids[:, :, None] == top[inv][:, None, :]).any(-1).sum(1)
    recall = float(np.where(good, hits, 0).mean() / k)

    floor = config["guarantees"]["recall_at_10_min"]
    tier2_limit = capacity * config["dim"] * 4
    checks = {
        "dist_gap": {"value": gap, "limit": DIST_GAP_LIMIT,
                     "ok": gap <= DIST_GAP_LIMIT},
        "recall_at_10": {"value": recall, "limit": floor,
                         "ok": recall >= floor},
        "bad_answers": {"value": int((~good).sum()), "limit": 0,
                        "ok": bool(good.all())},
        "tier2_bytes": {"value": int(tier2_bytes), "limit": tier2_limit,
                        "ok": tier2_bytes <= tier2_limit},
    }
    return checks, recall
