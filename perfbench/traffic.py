"""Traffic generation: one general generator, driven by a mix's data file.

A mix (``traffic/<name>.json``) names a ``kind`` and its parameters;
``generate`` turns it into a list of :class:`RequestSpec` for one run.

Every seed gets the same work: lengths come from the exact quantiles
of the stated distribution and inter-arrival gaps from the exact
quantiles of the exponential, dealt in rounds (``stratified``), each
of ``STRATA`` consecutive requests taking one value from each of
``STRATA`` quantile bands, so that every stretch of the window sees the
whole mix.  The order within the rounds is drawn once, from
``ORDER_SEED``, and is the same for every seed: a run is cut at the
close, so the order decides which requests it serves, and with a
seeded order the seed moved the tail of a cell by more than run-to-run
noise did.  The seed draws the token ids (and, in the harness, the
weights).

Kinds:

* ``poisson`` — open loop: ``rate_per_s`` arrivals, each with a prompt
  and an output length drawn from ``prompt`` and ``output``.  Arrivals
  begin ``warm_s`` seconds before the window opens (due times below 0),
  so that the window sees the engine at its steady load.
* ``backlog`` — offline: ``count`` requests all due at 0.

A length distribution is ``{"dist": "lognormal", "median", "sigma",
"min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()
STRATA = 8
ORDER_SEED = 0x6F72646572      # "order"


@dataclasses.dataclass
class RequestSpec:
    due_s: float                # offset from the opening of the window
    prompt: np.ndarray          # int32 token ids
    max_new: int


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles ``(i + 0.5) / n`` of
    ``dist``, clipped to its ``[min, max]``, in ascending order."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(n: int, mean_s: float) -> np.ndarray:
    """``n`` exponential gaps of mean ``mean_s`` at midpoint quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) * mean_s


def stratified(values: np.ndarray, rng: np.random.Generator,
               strata: int = STRATA) -> np.ndarray:
    """``values`` (ascending) reordered in rounds: round ``r`` holds the
    ``r``-th of a seeded draw from each of ``strata`` consecutive bands,
    in seeded order."""
    bands = np.array_split(np.asarray(values), strata)
    draws = [rng.permutation(b) for b in bands]
    out = []
    for r in range(max(len(b) for b in bands)):
        row = [d[r] for d in draws if r < len(d)]
        out.extend(rng.permutation(row))
    return np.asarray(out)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n), dtype=np.int32)


def _poisson(mix: Dict, seconds: float, vocab: int, rng: np.random.Generator,
             order: np.random.Generator) -> List[RequestSpec]:
    warm = float(mix.get("warm_s", 0.0))
    n = max(1, int(mix["rate_per_s"] * (warm + seconds)))
    gaps = stratified(exp_gaps(n, 1.0 / mix["rate_per_s"]), order)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) - warm
    plens = stratified(quantile_lengths(mix["prompt"], n), order)
    outs = stratified(quantile_lengths(mix["output"], n), order)
    return [RequestSpec(float(t), _tokens(rng, p, vocab), int(o))
            for t, p, o in zip(due, plens, outs) if t < seconds]


def _backlog(mix: Dict, seconds: float, vocab: int, rng: np.random.Generator,
             order: np.random.Generator) -> List[RequestSpec]:
    n = int(mix["count"])
    plens = stratified(quantile_lengths(mix["prompt"], n), order)
    outs = stratified(quantile_lengths(mix["output"], n), order)
    return [RequestSpec(0.0, _tokens(rng, p, vocab), int(o))
            for p, o in zip(plens, outs)]


def _fits(plen: int, max_new: int, max_len: int, chunk: int) -> bool:
    return (math.ceil(plen / chunk) * chunk <= max_len
            and plen + max_new <= max_len + 1)


def generate(mix: Dict, seed: int, seconds: float, vocab: int,
             chunk: int, max_len: int) -> List[RequestSpec]:
    """The requests of one run of ``mix``, in order of due time, for an
    engine of prefill chunk ``chunk`` and ``max_len`` positions."""
    rng = np.random.default_rng([seed, 0x7472616666])   # "traff"
    order = np.random.default_rng(ORDER_SEED)
    kind = mix["kind"]
    if kind == "poisson":
        specs = _poisson(mix, seconds, vocab, rng, order)
    elif kind == "backlog":
        specs = _backlog(mix, seconds, vocab, rng, order)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    for r in specs:
        if not _fits(len(r.prompt), r.max_new, max_len, chunk):
            raise ValueError(
                f"request of {len(r.prompt)} + {r.max_new} tokens does not "
                f"fit max_len {max_len}: fix the mix's clips")
    return specs
