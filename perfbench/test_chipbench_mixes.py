"""Every traffic mix, generated at the ``max_len`` its own ``engine``
block serves it with: reproducible from the seed, the same work for
every seed, and each round of ``traffic.STRATA`` requests holding one
length from each band."""
import json
import pathlib

import numpy as np
import pytest

import traffic

HERE = pathlib.Path(__file__).resolve().parent
MIXES = {p.stem: json.loads(p.read_text())
         for p in (HERE / "traffic").glob("*.json")}
SEED = 2**31 + 23456


def gen(name, seed=SEED, seconds=30.0):
    mix = MIXES[name]
    return traffic.generate(mix, seed, seconds, 50304, 32,
                            mix["engine"]["max_len"])


@pytest.mark.parametrize("name", sorted(MIXES))
def test_mix_same_seed_same_inputs(name):
    a, b = gen(name), gen(name)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    assert all(len(x.prompt) + x.max_new <= MIXES[name]["engine"]["max_len"]
               for x in a)
    c = gen(name, seed=SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_mix_every_seed_gets_the_same_work(name):
    a, b = gen(name), gen(name, seed=7)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_mix_every_round_holds_each_band(name):
    reqs = gen(name)
    bands = np.array_split(np.sort([len(r.prompt) for r in reqs]),
                           traffic.STRATA)
    edges = [b[-1] for b in bands[:-1]]
    k = traffic.STRATA
    for i in range(0, len(reqs) - k + 1, k):
        band = np.searchsorted(edges, [len(r.prompt) for r in reqs[i:i + k]],
                               side="left")
        assert len(set(band.tolist())) >= k - 1    # ties at band edges
