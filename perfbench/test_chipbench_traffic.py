"""The generator: reproducible from the seed, the stated medians and
clips, and the same work for every seed."""
import json
import pathlib

import numpy as np
import pytest

import traffic

HERE = pathlib.Path(__file__).resolve().parent
MIXES = {p.stem: json.loads(p.read_text())
         for p in (HERE / "traffic").glob("*.json")}
MAX_LEN = {"chat": 3584, "batch": 2048}
SEED = 2**31 + 12345


def gen(name, seed=SEED, seconds=30.0):
    return traffic.generate(MIXES[name], seed, seconds, 50304, 32,
                            MAX_LEN[name])


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_inputs(name):
    a, b = gen(name), gen(name)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    c = gen(name, seed=SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_every_seed_gets_the_same_work(name):
    """The same lengths and due times in the same order: the seed draws
    only the token ids."""
    a, b = gen(name), gen(name, seed=7)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 768, "sigma": 0.8, "min": 32,
     "max": 3072},
    {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 8, "max": 512},
    {"dist": "uniform", "min": 1024, "max": 1984}])
def test_lengths_have_the_stated_median_and_clips(dist):
    x = traffic.quantile_lengths(dist, 1001)
    assert x.min() >= dist["min"] and x.max() <= dist["max"]
    mid = dist.get("median", (dist["min"] + dist["max"]) / 2)
    assert abs(np.median(x) - mid) <= 1
    if dist["dist"] == "lognormal":
        assert x.max() == dist["max"]       # the tail reaches the clip


@pytest.mark.parametrize("warm_s", [0.0, 40.0])
def test_poisson_rate_window_and_lead_in(warm_s):
    mix = {**MIXES["chat"], "warm_s": warm_s}
    reqs = traffic.generate(mix, SEED, 30.0, 50304, 32, MAX_LEN["chat"])
    assert all(-warm_s <= r.due_s < 30 for r in reqs)
    assert reqs[0].due_s == -warm_s
    assert len(reqs) == int(mix["rate_per_s"] * (30 + warm_s))
    gaps = np.diff([r.due_s for r in reqs])
    assert gaps.mean() == pytest.approx(1 / mix["rate_per_s"], rel=0.1)
    window = [r for r in reqs if r.due_s >= 0]
    assert len(window) == pytest.approx(mix["rate_per_s"] * 30, abs=3)


def test_backlog_is_due_at_once():
    reqs = gen("batch")
    assert len(reqs) == MIXES["batch"]["count"]
    assert all(r.due_s == 0 for r in reqs)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_every_round_holds_each_band(name):
    """A run cut at the close serves a prefix of the requests: each round
    of STRATA consecutive requests holds one length from each band."""
    reqs = gen(name)
    bands = np.array_split(np.sort([len(r.prompt) for r in reqs]),
                           traffic.STRATA)
    edges = [b[-1] for b in bands[:-1]]
    k = traffic.STRATA
    for i in range(0, len(reqs) - k + 1, k):
        band = np.searchsorted(edges, [len(r.prompt) for r in reqs[i:i + k]],
                               side="left")
        assert len(set(band.tolist())) >= k - 1    # ties at band edges
