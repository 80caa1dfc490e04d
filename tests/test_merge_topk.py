"""Cross-shard top-k merge kernel vs oracles (DESIGN.md §10).

Separate from test_kernels.py because that module requires hypothesis;
the merge kernel underpins sharded/single-device bit-parity, so its
oracle tests must run in every tier-1 environment.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.topk import merge_topk_pallas

RNG = np.random.default_rng(0)


def np_merge_topk(d, i, k):
    """Independent pure-numpy oracle: drop sentinels, sort by
    (dist, input position), dedup ids keeping the best copy, take k."""
    B, M = d.shape
    od = np.full((B, k), np.inf, np.float32)
    oi = np.full((B, k), -1, np.int32)
    osrc = np.full((B, k), -1, np.int32)
    for b in range(B):
        ents = sorted(
            (float(d[b, m]), m, int(i[b, m]))
            for m in range(M)
            if i[b, m] >= 0 and np.isfinite(d[b, m])
        )
        seen, out = set(), []
        for dist, pos, gid in ents:
            if gid in seen:
                continue
            seen.add(gid)
            out.append((dist, pos, gid))
            if len(out) == k:
                break
        for j, (dist, pos, gid) in enumerate(out):
            od[b, j], oi[b, j], osrc[b, j] = dist, gid, pos
    return od, oi, osrc


def _check(d, i, k):
    d = np.asarray(d, np.float32)
    i = np.asarray(i, np.int32)
    want = np_merge_topk(d, i, k)
    got_ref = ref.merge_topk_ref(jnp.asarray(d), jnp.asarray(i), k)
    got_krn = merge_topk_pallas(jnp.asarray(d), jnp.asarray(i), k,
                                interpret=True)
    for name, got in (("ref", got_ref), ("pallas", got_krn)):
        for w, g, what in zip(want, got, ("dists", "ids", "src")):
            np.testing.assert_array_equal(
                np.asarray(g), w, err_msg=f"{name} {what} (k={k})"
            )


def _rand_case(rng, B, M, n_ids, p_sentinel=0.2):
    d = rng.standard_normal((B, M)).astype(np.float32) ** 2
    i = rng.integers(0, n_ids, size=(B, M)).astype(np.int32)
    i = np.where(rng.random((B, M)) < p_sentinel, -1, i)
    return d, i


# ---------------------------------------------------------- hand cases


def test_dedup_keeps_best_copy():
    # id 9 arrives from two "shards"; id 3 from two with distinct dists
    d = np.array([[5.0, 2.0, 2.0, 7.0, 2.0]], np.float32)
    i = np.array([[3, 9, 9, 3, 4]], np.int32)
    od, oi, osrc = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 4)
    np.testing.assert_array_equal(np.asarray(oi), [[9, 4, 3, -1]])
    np.testing.assert_array_equal(np.asarray(osrc), [[1, 4, 0, -1]])
    np.testing.assert_array_equal(np.asarray(od), [[2.0, 2.0, 5.0, np.inf]])
    _check(d, i, 4)


def test_ties_break_by_lower_input_position():
    # all-equal dists → output order must equal input order (beam_merge /
    # lax.top_k tie semantics the sharded driver depends on)
    d = np.zeros((1, 6), np.float32)
    i = np.array([[10, 11, 12, 13, 14, 15]], np.int32)
    _, oi, osrc = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 6)
    np.testing.assert_array_equal(np.asarray(oi), i)
    np.testing.assert_array_equal(np.asarray(osrc), [[0, 1, 2, 3, 4, 5]])
    _check(d, i, 6)


def test_sentinels_never_win():
    d = np.array([[np.nan, 0.5, -np.inf, np.inf, 1.5, 0.25]], np.float32)
    i = np.array([[1, 2, 3, 4, -1, 6]], np.int32)
    od, oi, _ = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 4)
    # only ids 2 and 6 are usable: nan/±inf dists and id -1 are sentinels
    np.testing.assert_array_equal(np.asarray(oi), [[6, 2, -1, -1]])
    np.testing.assert_array_equal(
        np.asarray(od), [[0.25, 0.5, np.inf, np.inf]]
    )
    _check(d, i, 4)


def test_all_sentinel_row():
    d = np.full((2, 5), 1.0, np.float32)
    i = np.full((2, 5), -1, np.int32)
    i[1, 2] = 7
    od, oi, osrc = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 3)
    np.testing.assert_array_equal(np.asarray(oi[0]), [-1, -1, -1])
    np.testing.assert_array_equal(np.asarray(osrc[0]), [-1, -1, -1])
    assert np.isinf(np.asarray(od[0])).all()
    np.testing.assert_array_equal(np.asarray(oi[1]), [7, -1, -1])
    _check(d, i, 3)


def test_k_exceeds_candidates():
    d = np.array([[3.0, 1.0]], np.float32)
    i = np.array([[5, 8]], np.int32)
    od, oi, osrc = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 5)
    np.testing.assert_array_equal(np.asarray(oi), [[8, 5, -1, -1, -1]])
    np.testing.assert_array_equal(np.asarray(osrc), [[1, 0, -1, -1, -1]])
    _check(d, i, 5)


# ------------------------------------------------------------- sweeps


@pytest.mark.parametrize(
    "B,M,k",
    [
        (1, 1, 1),
        (3, 7, 3),  # odd M
        (8, 44, 11),  # non-pow2 M, duplicates likely (n_ids small)
        (5, 130, 16),  # M spills past one MERGE_TM lane block
        (2, 3, 9),  # k > M
        (16, 96, 64),  # k at beam scale
    ],
)
def test_merge_random_shapes(B, M, k):
    d, i = _rand_case(np.random.default_rng(B * 1000 + M + k), B, M,
                      n_ids=max(2, M // 2))
    _check(d, i, k)


def test_merge_random_trials():
    rng = np.random.default_rng(42)
    for _ in range(25):
        B = int(rng.integers(1, 9))
        M = int(rng.integers(1, 45))
        k = int(rng.integers(1, 12))
        d, i = _rand_case(rng, B, M, n_ids=int(rng.integers(2, 60)))
        # sprinkle non-finite dists on live ids too
        bad = rng.random((B, M)) < 0.1
        d = np.where(bad, rng.choice([np.nan, np.inf, -np.inf], (B, M)), d)
        _check(d.astype(np.float32), i, k)


def test_ops_dispatch_matches_ref():
    d, i = _rand_case(np.random.default_rng(5), 6, 30, n_ids=12)
    got = ops.merge_topk(jnp.asarray(d), jnp.asarray(i), 8)
    want = ref.merge_topk_ref(jnp.asarray(d), jnp.asarray(i), 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
