"""The traffic generator: seeded, clipped, and the same amount of work
for every seed."""

import json
import os
from collections import Counter

import numpy as np
import pytest

from benchmarks.chip import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIXES = ["decode", "prefill"]


def _mix(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "traffic",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.job(mix, 2**31 + 5, 3, 151936)
    b = traffic.job(mix, 2**31 + 5, 3, 151936)
    assert len(a) == mix["job_requests"]
    for (pa, oa), (pb, ob) in zip(a, b):
        assert oa == ob and np.array_equal(pa, pb)
    c = traffic.job(mix, 2**31 + 6, 3, 151936)
    assert any(not np.array_equal(pa, pc) for (pa, _), (pc, _) in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips_and_same_work_per_seed(name):
    """Every job holds the same lengths; every seed runs them in the same
    order (a job's own), so only the token ids differ."""
    mix = _mix(name)
    jobs = [traffic.job(mix, s, i, 151936) for s in (1, 99, 2**32 + 1)
            for i in range(2)]
    for job in jobs:
        for p, o in job:
            assert mix["prompt"]["min"] <= len(p) <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= o <= mix["output"]["max"]
            assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 151936
    multisets = {(tuple(sorted(Counter(len(p) for p, _ in j).items())),
                  tuple(sorted(Counter(o for _, o in j).items())))
                 for j in jobs}
    assert len(multisets) == 1
    schedules = [[(len(p), o) for p, o in j] for j in jobs]
    assert schedules[0] == schedules[2] == schedules[4]
    assert schedules[1] == schedules[3] == schedules[5]
    assert schedules[0] != schedules[1]


def test_quantile_lengths_by_hand():
    """Four quantiles of uniform [1, 4] are 1, 2, 3, 4; the lognormal's
    middle quantile of an odd count is its median, clipped ends stay put."""
    assert traffic.quantile_lengths(
        {"dist": "uniform", "min": 1, "max": 4}, 4) == [1, 2, 3, 4]
    ln = traffic.quantile_lengths(
        {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16,
         "max": 256}, 5)
    assert ln[2] == 96 and ln == sorted(ln)
    wide = traffic.quantile_lengths(
        {"dist": "lognormal", "median": 96, "sigma": 5.0, "min": 16,
         "max": 256}, 5)
    assert wide[0] == 16 and wide[-1] == 256
    assert traffic.longest(_mix("decode")) == (97, 256)
    assert traffic.shortest_prompt(_mix("prefill")) == 128


def test_decode_mix_keeps_the_cited_means():
    """The decode mix's lengths average what its source measured (Alpaca
    in the vLLM paper: 19.31 prompt and 58.45 output tokens), within 1%."""
    mix = _mix("decode")
    assert "19.31" in mix["source"] and "58.45" in mix["source"]
    n = mix["job_requests"]
    for dist, mean in ((mix["prompt"], 19.31), (mix["output"], 58.45)):
        got = np.mean(traffic.quantile_lengths(dist, n))
        assert abs(got / mean - 1) < 0.01
    assert sum(traffic.longest(mix)) <= mix["max_len"]
