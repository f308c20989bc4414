"""The reduction from a trace to device busy time, idle gaps and the
breakdown, on a hand-made trace and on a small trace recorded on a v5e."""
import glob
import os

import pytest

from conftest import DATA

import harness


def test_union_idle_and_breakdown_by_hand():
    # ns: ops [0,10) [5,20) [30,40) [38,45) [60,70); slice [2, 65)
    events = {"device": [["a", 0, 10], ["b", 5, 15], ["a", 30, 10],
                         ["c", 38, 7], ["b", 60, 10]],
              "host": [["bench.slice", 2, 63], ["bench.stage", 18, 15],
                       ["bench.run_chunk", 44, 30]]}
    lo, hi = harness.slice_bounds(events)
    assert (lo, hi) == (2, 65)
    # busy: [2,20) 18 + [30,45) 15 + [60,65) 5 = 38 of 63
    assert harness.busy_ns(events, lo, hi) == 38
    assert harness.idle_gaps(events, lo, hi) == [(20, 30), (45, 60)]
    bd = harness.breakdown(events, lo, hi)
    assert [n for n, _ in bd["device_ops"]] == ["b", "a", "c"]
    assert [v for _, v in bd["device_ops"]] == pytest.approx(
        [20e-9, 18e-9, 7e-9])
    # gap (45,60) has its middle in run_chunk; (20,30) in stage
    assert [n for n, _ in bd["idle_gaps"]] == ["bench.run_chunk",
                                               "bench.stage"]
    assert [v for _, v in bd["idle_gaps"]] == pytest.approx([15e-9, 10e-9])


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.events.json.gz"))))
def test_recorded_trace(path):
    """A trace recorded on the chip reduces to a busy share in (0, 1] and
    a breakdown whose op time does not exceed the slice."""
    events = harness.load_events(path)
    lo, hi = harness.slice_bounds(events)
    busy = harness.busy_ns(events, lo, hi)
    assert 0 < busy <= hi - lo
    gaps = harness.idle_gaps(events, lo, hi)
    assert abs(sum(e - s for s, e in gaps) + busy - (hi - lo)) < 1e-3 * (
        hi - lo)
    bd = harness.breakdown(events, lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] <= (hi - lo) / 1e9
