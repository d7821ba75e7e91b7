"""The generator: the seed orders the work, it does not change it."""

import json
import os

import numpy as np
import pytest

from benchmark.traffic import (ServeTraffic, stratified_gaps,
                               stratified_lengths, stream_frames)

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")

OPEN = {"kind": "serve",
        "arrival": {"mode": "poisson", "rate_per_s": 3.0,
                    "ramp_seconds": 2.0},
        "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                       "min": 8, "max": 512},
        "max_new": 128, "check_requests": 4}
BURSTY = dict(OPEN, arrival={"mode": "bursty", "rate_per_s": 4.0,
                             "burst_size": 8, "intra_gap_s": 0.01,
                             "ramp_seconds": 1.0})
CLOSED = {"kind": "serve", "arrival": {"mode": "closed", "clients": 32},
          "prompt_len": {"dist": "uniform", "min": 16, "max": 32},
          "max_new": 512, "check_requests": 2}


def _plan(mix, seed, seconds=40.0):
    reqs = ServeTraffic(mix, 32000, seed, seconds).schedule()
    return ([r.due_s for r in reqs], [len(r.prompt) for r in reqs],
            [r.prompt.tolist() for r in reqs])


@pytest.mark.parametrize("mix", [OPEN, BURSTY], ids=["poisson", "bursty"])
def test_same_seed_same_schedule_other_seed_other_order(mix):
    a, b, c = _plan(mix, 7), _plan(mix, 7), _plan(mix, 8)
    assert a == b
    assert a[0] != c[0] and a[1] != c[1] and a[2] != c[2]
    # the same work in another order: equal multisets of lengths and gaps
    assert sorted(a[1]) == sorted(c[1])
    assert len(a[0]) == len(c[0])
    if mix is OPEN:
        # every gap between arrivals is one of the same set of quantiles
        # (the first arrival's own gap is the one a diff cannot show)
        canon = stratified_gaps(len(a[0]), 42.0 / len(a[0]))
        for due in (a[0], c[0]):
            gaps = np.diff(due)
            assert np.isclose(gaps[:, None], canon[None, :],
                              atol=1e-9).any(axis=1).all()
            assert np.isclose(canon.sum() - gaps.sum(), canon,
                              atol=1e-9).any()


def test_open_loop_covers_ramp_and_window_at_the_rate():
    due, lens, _ = _plan(OPEN, 3, seconds=40.0)
    assert len(due) == round(3.0 * 42.0)
    assert -2.0 <= min(due) < 0.0 and 38.0 < max(due) < 40.0
    assert due == sorted(due)
    assert min(lens) >= 8 and max(lens) <= 512
    assert 40 <= float(np.median(lens)) <= 56


def test_bursts_arrive_together():
    due, _, _ = _plan(BURSTY, 5)
    gaps = np.diff(due)
    assert (gaps < 0.011).mean() > 0.8   # 7 of every 8 follow at once


def test_closed_loop_draws_the_whole_multiset_each_cycle():
    t = ServeTraffic(CLOSED, 32000, 2**31 + 12345, 10.0)
    it = t.closed_prompts()
    first = [len(next(it).prompt) for _ in range(t.CLOSED_CYCLE)]
    second = [len(next(it).prompt) for _ in range(t.CLOSED_CYCLE)]
    assert sorted(first) == sorted(second) and first != second
    assert set(first) == set(range(16, 33))
    assert t.clients == 32 and t.max_prompt == 32 and t.max_new == 512


def test_shared_prefix_is_shared_and_sized_for():
    mix = dict(OPEN, shared_prefix={"tokens": 64, "groups": 1})
    t = ServeTraffic(mix, 32000, 1, 10.0)
    reqs = t.schedule()
    assert all((r.prompt[:64] == reqs[0].prompt[:64]).all() for r in reqs)
    assert t.max_prompt == 512 + 64


def test_quantile_sets():
    assert stratified_lengths({"dist": "uniform", "min": 16, "max": 32},
                              17).tolist() == list(range(16, 33))
    assert abs(stratified_gaps(1000, 0.25).mean() - 0.25) < 1e-12


def test_frames_follow_the_seed():
    mix = {"batch": 4, "pool_batches": 2}
    a, b, c = (stream_frames(mix, 16, s) for s in (9, 9, 10))
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    assert a[0].shape == (4, 16, 16, 3) and a[0].dtype == np.uint8
    assert not (a[0] == a[1]).all()


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(MIXES) if f.endswith(".json")))
def test_committed_mixes_parse(name):
    with open(os.path.join(MIXES, name)) as f:
        mix = json.load(f)
    if mix["kind"] == "serve":
        t = ServeTraffic(mix, 32000, 1, 5.0)
        if t.mode == "closed":
            assert next(t.closed_prompts()).prompt.size >= 1
        else:
            assert t.schedule()
    else:
        assert mix["batch"] > 0 and mix["max_inflight"] > 0
