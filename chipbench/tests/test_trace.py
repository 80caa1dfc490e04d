"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (``chipbench/testdata/``)."""

import os

import pytest

from chipbench import trace as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata")


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_gaps_cover_what_busy_leaves_in_the_window():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps(busy, 2, 7) == [(4, 6)]


def test_idle_time_goes_to_the_innermost_span_piece_by_piece():
    spans = [("request", 0, 100), ("tier3_fetch", 40, 60)]
    idle = [(10, 20), (30, 50), (90, 120)]
    assert tr.attribute(idle, spans) == {
        "request": 10 + 10 + 10, "tier3_fetch": 10, tr.NO_SPAN: 20}


def test_recorded_trace_reduces_to_the_hand_counted_values(tmp_path):
    """One 16-query request of wiki768-lazy-b16 traced on a TPU v5e.
    The expected values were counted by a separate sweep over the raw
    events: busy is the covered length of the XLA Ops intervals, launches
    are the XLA Modules events that start inside the request span, and
    each idle piece inside a tier3_fetch span belongs to it."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(os.path.join(
            TESTDATA, "lazy-b16-one-request.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    s = tr.summarize(pd, ("request", "tier3_fetch"))
    assert s.n_requests == 1
    assert s.window_s == pytest.approx(0.422320514, abs=1e-12)
    assert s.busy_s == pytest.approx(0.031991689, abs=1e-12)
    assert s.idle_share == pytest.approx(0.924247845085735, abs=1e-12)
    assert s.launches == 615
    assert dict(s.idle_gaps) == pytest.approx(
        {"request": 0.388116534, "tier3_fetch": 0.002212291}, abs=1e-12)
    assert s.device_ops[0][0].startswith("jit__batch_phase_cached")
