"""The reduction from a profiler trace to busy time, idle shares and gaps.

Hand-made traces check the arithmetic; a small trace recorded on a TPU v5e
chip (``testdata/tiny_v5e.xplane.pb.gz``: two jobs of a tiny logistic
regression under the harness's spans) checks that the reader finds the
spans and the chip's operations where the profiler puts them.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cell, trace  # noqa: E402

RECORDED = HERE / "testdata" / "tiny_v5e.xplane.pb.gz"

# one chip; window 0..100; sample 0..60, combine 60..100
HAND = trace.Trace(
    spans=[("job", 0.0, 100.0), ("sample", 0.0, 60.0), ("combine", 60.0, 100.0)],
    host=[("job", 0.0, 100.0), ("PjitFunction(step)", 5.0, 8.0),
          ("sample", 0.0, 60.0), ("combine", 60.0, 100.0),
          ("PjitFunction(img)", 70.0, 90.0)],
    devices=[[("matvec", 10.0, 30.0), ("logsig", 20.0, 40.0),
              ("matvec", 50.0, 55.0), ("scan", 65.0, 75.0)]],
)


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.union([]) == []


def test_covered_clips_to_the_stretch():
    merged = [(0.0, 4.0), (5.0, 6.0), (10.0, 20.0)]
    assert trace.covered(merged, 3.0, 12.0) == 1.0 + 1.0 + 2.0
    assert trace.covered(merged, 6.0, 10.0) == 0.0


def test_idle_shares_by_hand():
    # busy: 10..40, 50..55, 65..75 -> 30 + 5 + 10 = 45 of 100
    assert trace.idle_share(HAND) == pytest.approx(0.55)
    # sample 0..60 holds 35 busy; combine 60..100 holds 10
    assert trace.idle_share(HAND, "sample") == pytest.approx(25 / 60)
    assert trace.idle_share(HAND, "combine") == pytest.approx(30 / 40)
    busy_s, window_s = trace.busy_seconds(HAND)
    assert busy_s == pytest.approx(45e-9) and window_s == pytest.approx(100e-9)


def test_idle_share_averages_over_chips():
    two = HAND._replace(devices=HAND.devices + [[("matvec", 0.0, 100.0)]])
    assert trace.idle_share(two) == pytest.approx(0.55 / 2)


def test_top_ops_and_gaps_by_hand():
    ops = dict(trace.top_ops(HAND))
    assert ops["matvec"] == pytest.approx(25e-9)
    assert ops["logsig"] == pytest.approx(20e-9)
    gaps = trace.idle_gaps(HAND)
    # gaps: 0..10 (sample), 40..50 (sample), 55..65 (sample->combine
    # boundary at 60), 75..100 (combine, inside PjitFunction(img) at 87.5)
    assert [round(g[1] * 1e9) for g in gaps] == [25, 10, 10, 10]
    assert gaps[0][0] == "combine/PjitFunction(img)"
    assert {g[0] for g in gaps[1:]} <= {"sample", "combine", "sample/PjitFunction(step)"}


def test_no_device_ops_reads_nothing():
    empty = HAND._replace(devices=[])
    assert trace.idle_share(empty) is None
    assert trace.busy_seconds(empty) is None
    assert trace.top_ops(empty) == [] and trace.idle_gaps(empty) == []
    ctx = {"trace": empty, "cell": None, "jobs": 1, "peak": None}
    for name in ("sample_idle", "combine_idle", "device_idle", "mfu"):
        assert cell.reader(name).read(ctx) is None


def _independent_busy(ops, lo, hi):
    """Busy time by a sweep over 1 ns-free event boundaries."""
    points = sorted({lo, hi} | {max(lo, min(hi, t)) for _, s, e in ops for t in (s, e)})
    total = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for _, s, e in ops):
            total += b - a
    return total


def test_recorded_chip_trace():
    tr = trace.load(RECORDED, chips=1)
    names = [n for n, _, _ in tr.spans]
    assert names.count("job") == 2 and names.count("sample") == 2
    assert names.count("combine") == 2
    assert len(tr.devices) == 1 and len(tr.devices[0]) > 10
    lo, hi = trace.window(tr)
    busy_s, window_s = trace.busy_seconds(tr)
    assert window_s == pytest.approx((hi - lo) * 1e-9)
    assert busy_s == pytest.approx(_independent_busy(tr.devices[0], lo, hi) * 1e-9, rel=1e-9)
    assert 0.0 < busy_s < window_s
    share = trace.idle_share(tr)
    assert share == pytest.approx(1.0 - busy_s / window_s)
    # every job span holds its sample and combine spans
    jobs = [(s, e) for n, s, e in tr.spans if n == "job"]
    for n, s, e in tr.spans:
        if n != "job":
            assert any(js <= s and e <= je for js, je in jobs)
    # device ops run inside the traced window, on the host's clock
    inside = [op for op in tr.devices[0] if lo <= op[1] <= hi]
    assert len(inside) > 10
