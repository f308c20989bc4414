"""The generator gives every seed the same work: the same sets of sizes and
arrival gaps, and the same last requests, in another order."""
import numpy as np
import pytest

import traffic

SEEDS = [1, 2 ** 31 + 11, 2 ** 33 + 7]


def test_every_seed_gets_the_same_work():
    mix = traffic.load("chat-poisson")
    runs = [traffic.serve_requests(mix, 35.0, s, 1000) for s in SEEDS]
    n = round(mix["rate"] * 35.0)
    k = round(mix["rate"] * mix["fixed_tail_s"])
    for reqs in runs:
        assert len(reqs) == n
        assert sorted(r["steps"] for r in reqs) == sorted(
            r["steps"] for r in runs[0])
        assert reqs[-1]["arrival"] == pytest.approx(runs[0][-1]["arrival"])
        # the tail: the same gaps and lengths in the same order
        assert [r["steps"] for r in reqs[-k:]] == [
            r["steps"] for r in runs[0][-k:]]
        assert np.allclose(np.diff([r["arrival"] for r in reqs[-k - 1:]]),
                           np.diff([r["arrival"] for r in runs[0][-k - 1:]]))
    # the seed still draws the order before the tail and every prompt
    assert [r["steps"] for r in runs[0][:-k]] != [
        r["steps"] for r in runs[1][:-k]]
    assert not np.array_equal(runs[0][-1]["prompt"], runs[1][-1]["prompt"])


def test_the_same_seed_gives_the_same_inputs():
    mix = traffic.load("chat-poisson")
    a, b = (traffic.serve_requests(mix, 10.0, 2 ** 33 + 7, 1000)
            for _ in range(2))
    assert [(r["arrival"], r["steps"], r["tenant"]) for r in a] == [
        (r["arrival"], r["steps"], r["tenant"]) for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
