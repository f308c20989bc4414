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


# The one-chip readings of the recorded traces as the harness gave them
# before it read several devices: mfu.train at one round of the training
# cell's 24190463770624 FLOPs, the two idle shares, busy_s and window_s,
# and the breakdown (its op and gap seconds, and a digest of the whole).
PINNED = {
    "v5e-serve-qwen3-8b-l6-poisson.events.json.gz": {
        "mfu.train": 61.39711616909645, "device_idle.train": 17.894855,
        "device_idle.serve": 17.894855, "busy_s": 0.16421029,
        "window_s": 0.2, "digest": "679952fb774064ca",
        "ops_s": [0.10104876100000001, 0.066347557, 0.019850683999999997,
                  0.011450113, 0.009325917999999999, 0.009311094,
                  0.009291260999999999, 0.0073541859999999995, 0.007337425,
                  0.005754398],
        "gaps_s": [0.005119187, 0.004610426, 0.004459515, 0.003830934,
                   0.003823568, 0.003427966, 0.003327597, 0.003050683,
                   0.002563722, 0.000761862]},
    "v5e-train-stablelm-1.6b-n8r64.events.json.gz": {
        "mfu.train": 61.39711616909645, "device_idle.train": 0.0,
        "device_idle.serve": 0.0, "busy_s": 0.2, "window_s": 0.2,
        "digest": "049fc0e139f44fbd",
        "ops_s": [0.2, 0.2, 0.095103737, 0.033696049, 0.016696823,
                  0.011699838, 0.010199123000000006, 0.009453652999999994,
                  0.009095868, 0.008980017],
        "gaps_s": []},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_one_chip_readings_as_before(name):
    """Reading several devices leaves a one-chip trace's numbers as they
    were, to the last digit."""
    import hashlib
    import json
    want = PINNED[name]
    events = harness.load_events(os.path.join(DATA, name))
    lo, hi = harness.slice_bounds(events)
    ctx = {"events": events, "slice": (lo, hi), "rounds_in_slice": 1,
           "round_flops": 24190463770624.0, "chips": 1,
           "peaks": harness.peaks("TPU v5 lite")}
    for metric in ("mfu.train", "device_idle.train", "device_idle.serve"):
        assert harness.read_metric(metric, ctx) == want[metric], metric
    assert harness.read_metric("collective_share.train", ctx) is None
    assert harness.mean_busy_ns(events, lo, hi) / 1e9 == want["busy_s"]
    assert harness.busy_ns(events, lo, hi) / 1e9 == want["busy_s"]
    assert (hi - lo) / 1e9 == want["window_s"]
    bd = harness.breakdown(events, lo, hi)
    assert [v for _, v in bd["device_ops"]] == want["ops_s"]
    assert [v for _, v in bd["idle_gaps"]] == want["gaps_s"]
    assert hashlib.sha256(json.dumps(bd).encode()).hexdigest()[:16] == (
        want["digest"])


def _two_devices():
    # ns; slice [0, 100).  Device 0: a matmul [0, 60), an all-reduce
    # [60, 70), an all-gather's start [70, 72) and done [80, 90) (its
    # flight between them, [72, 80), is the device's own to overlap).
    # Device 1: a fusion [0, 50), a reduce-scatter [50, 80) with a
    # collective-permute [75, 85) overlapping it.
    dev0 = [["%fusion.1 = f32[8] fusion(...)", 0, 60],
            ["%all-reduce.3 = f32[8] all-reduce(...)", 60, 10],
            ["%all-gather-start.1 = (f32[2], f32[8]) all-gather-start(...)",
             70, 2],
            ["%all-gather-done.1 = f32[8] all-gather-done(...)", 80, 10]]
    dev1 = [["%fusion.2 = f32[8] fusion(...)", 0, 50],
            ["%reduce-scatter.7 = f32[2] reduce-scatter(...)", 50, 30],
            ["%collective-permute-done = f32[8] collective-permute-done()",
             75, 10]]
    return {"device": dev0, "devices": [dev0, dev1],
            "host": [["bench.slice", 0, 100]]}


def test_collective_share_by_hand():
    events = _two_devices()
    ctx = {"events": events, "slice": harness.slice_bounds(events)}
    # device 0: 10 + 2 + 10 = 22 of 100; device 1: [50, 85) = 35 of 100
    assert harness.read_metric("collective_share.train", ctx) == (
        pytest.approx((22 + 35) / 2))
    one = {**events, "devices": [events["devices"][1]],
           "device": events["devices"][1]}
    assert harness.read_metric("collective_share.train", {
        **ctx, "events": one}) == pytest.approx(35)
    none = {**events, "devices": [[["%fusion.1 = f32[8] fusion(...)", 0,
                                    60]]]}
    assert harness.read_metric("collective_share.train", {
        **ctx, "events": none}) is None


def test_idle_and_mfu_over_devices_by_hand():
    """``device_idle.train`` is the mean of the devices' idle shares, busy
    time the mean of their busy times, and ``mfu.train`` divides by every
    chip's peak."""
    events = _two_devices()
    lo, hi = harness.slice_bounds(events)
    # busy: device 0 [0, 72) + [80, 90) = 82; device 1 [0, 85) = 85
    assert harness.mean_busy_ns(events, lo, hi) == pytest.approx(83.5)
    ctx = {"events": events, "slice": (lo, hi), "rounds_in_slice": 2,
           "round_flops": 1e-7, "peaks": {"flops_bf16": 1e3}}
    assert harness.read_metric("device_idle.train", ctx) == (
        pytest.approx(16.5))
    one = harness.read_metric("mfu.train", {**ctx, "chips": 1})
    assert one == pytest.approx(100 * 2 * 1e-7 / (100e-9 * 1e3))
    assert harness.read_metric("mfu.train", {**ctx, "chips": 4}) == (
        pytest.approx(one / 4))
