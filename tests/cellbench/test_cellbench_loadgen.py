"""The load generator: the same seed gives the same work, every seed
gets the same set of sizes and gaps in an order of its own, lengths
stay inside their clips, and generators and distributions are found by
the names a mix gives."""

import json

import numpy as np
import pytest

from cellbench_tiny import REPO

from cellbench import loadgen
from cellbench.generators import open_loop, train_batches

BIG_SEED = 2 ** 31 + 12345
MIXES = sorted(p.stem for p in (REPO / "cellbench" / "traffic").glob("*.json")
               if json.loads(p.read_text())["generator"] == "open_loop")


def _mix(name, rate=4.0):
    mix = json.loads((REPO / "cellbench" / "traffic" / f"{name}.json")
                     .read_text())
    mix["arrivals"]["rate"] = mix["arrivals"]["rate"] or rate
    return mix


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = open_loop.requests(_mix(name), 50304, BIG_SEED, 40.0)
    b = open_loop.requests(_mix(name), 50304, BIG_SEED, 40.0)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_and_gaps(name):
    a = open_loop.requests(_mix(name), 50304, 1, 40.0)
    b = open_loop.requests(_mix(name), 50304, BIG_SEED, 40.0)
    assert len(a) == len(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.due for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_the_order_is_drawn_so_clumps_occur(name):
    """The seed shuffles the set: over seeds, the share of a window's
    output tokens that falls due in its first quarter swings as a drawn
    sample's would (an even interleaving would hold it at 0.25), and no
    two seeds share an order."""
    shares, orders = [], set()
    for seed in range(40):
        reqs = open_loop.requests(_mix(name), 50304, BIG_SEED + seed, 48.0)
        total = sum(r.max_new_tokens for r in reqs)
        shares.append(sum(r.max_new_tokens for r in reqs if r.due < 12.0)
                      / total)
        orders.add(tuple(r.max_new_tokens for r in reqs))
    assert len(orders) == 40
    assert abs(np.mean(shares) - 0.25) < 0.03
    assert max(shares) - min(shares) > 0.1


def test_gaps_are_exponential_and_lengths_lognormal():
    """The set pictures the distributions the mix names: the gaps' mean
    is 1/rate and their standard deviation about their mean (a Poisson
    process), the log of the unclipped lengths has the mix's sigma."""
    mix = _mix(MIXES[0])
    reqs = open_loop.requests(mix, 50304, 3, 400.0)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert abs(gaps.mean() * mix["arrivals"]["rate"] - 1.0) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    o = mix["lengths"]["output"]
    outs = np.array([r.max_new_tokens for r in reqs], float)
    inner = outs[(outs > o["min"]) & (outs < o["max"])]
    assert abs(np.median(outs) - o["median"]) <= 1
    z = loadgen.quantile_set({"dist": "lognormal", "median": 1.0,
                              "sigma": o["sigma"]}, 2000)
    assert abs(np.log(z).std() - o["sigma"]) < 0.01
    assert np.log(inner).std() < o["sigma"]          # the clips cut tails


def test_generators_and_distributions_are_found_by_name():
    assert loadgen.generator({"generator": "open_loop"}) is open_loop
    assert loadgen.generator({"generator": "train_batches"}) is train_batches
    with pytest.raises(ValueError, match="generators/closed_burst.py"):
        loadgen.generator({"generator": "closed_burst"})
    with pytest.raises(ValueError, match="dists/gamma.py"):
        loadgen.quantile_set({"dist": "gamma", "shape": 0.25}, 4)
    np.testing.assert_allclose(       # the median of three is the middle one
        loadgen.quantile_set({"dist": "exponential"}, 3)[1], np.log(2.0))
    for traffic in (REPO / "cellbench" / "traffic").glob("*.json"):
        mix = json.loads(traffic.read_text())
        assert loadgen.generator(mix)
        # a mix holds no key that nothing reads
        assert set(mix) <= {"generator", "arrivals", "lengths", "why",
                            "knee", "in_flight_at_open", "global_batch",
                            "tokens", "lr", "prefetch"}


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_their_clips_and_due_inside_the_window(name):
    mix = _mix(name)
    reqs = open_loop.requests(mix, 50304, 7, 40.0)
    p, o = mix["lengths"]["prompt"], mix["lengths"]["output"]
    assert len(reqs) == round(mix["arrivals"]["rate"] * 40.0)
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert len(r.prompt) + r.max_new_tokens <= 1024
        assert 0.0 < r.due < 40.0
        assert all(0 <= t < 50304 for t in r.prompt)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    # the set of lengths pictures the distribution: its median is the mix's
    assert abs(np.median([len(r.prompt) for r in reqs]) - p["median"]) <= 2
    assert abs(np.median([r.max_new_tokens for r in reqs])
               - o["median"]) <= 2


def test_an_unset_rate_is_an_error():
    mix = _mix(MIXES[0])
    mix["arrivals"]["rate"] = None
    with pytest.raises(ValueError, match="rate"):
        open_loop.requests(mix, 50304, 1, 10.0)


def test_train_batches_repeat_and_rows_differ():
    mix = {"global_batch": 8, "tokens": "uniform"}
    a = train_batches.first_batches(mix, 50304, 128, BIG_SEED, 3)
    b = train_batches.first_batches(mix, 50304, 128, BIG_SEED, 3)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert x.shape == (8, 129) and x.dtype == np.int32
        assert 0 <= x.min() and x.max() < 50304
    rows = np.concatenate(a)
    assert len({r.tobytes() for r in rows}) == len(rows)
    c = train_batches.first_batches(mix, 50304, 128, 1, 1)
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("name", MIXES)
def test_requests_in_flight_at_the_open(name):
    mix = _mix(name)
    held = open_loop.in_flight_at_open(mix, 50304, BIG_SEED)
    assert held == open_loop.in_flight_at_open(mix, 50304, BIG_SEED)
    assert len(held) == mix["in_flight_at_open"] > 0
    p, o = mix["lengths"]["prompt"], mix["lengths"]["output"]
    for r in held:
        assert r.due == 0.0
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert 1 <= r.max_new_tokens <= o["max"]     # what is left of it
    assert open_loop.in_flight_at_open(dict(mix, in_flight_at_open=0),
                                     50304, 1) == []
