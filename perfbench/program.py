"""Every point at which the benchmark touches the program under test.

The benchmark takes from the program only the serve engine, its spans
and counters, and the names its kernels carry in a device trace:

* ``repro.configs.get_config(registry, **overrides)`` — the model;
* ``repro.models.model.init_params`` — only through ``jax.eval_shape``,
  for the tree the engine expects the weights in;
* ``repro.serve.engine.ServeEngine(cfg, params, batch_size, max_len,
  session=, kv_layout="paged", kv_page_size=, kv_pool_pages=,
  prefill_chunk=, greedy=True, cache_dtype="bfloat16")``, its ``generate``,
  ``drain``, ``stats()``, ``stall_events`` and ``compile_counts``;
* ``Request(prompt, max_new_tokens, deadline_s=)`` and the admission
  gate ``Request._retry_at`` (monotonic seconds), which holds each
  request back until it is due;
* ``Request.out``: the engine gives each admitted request a fresh list
  and appends or extends it as tokens reach the host (``TimedRequest``
  notes when);
* ``repro.core.Session(["tpu"])`` with a ``MemoryExporter``: the spans
  ``serve/req<N>`` (admission to last token), ``serve/req<N>/prefill``
  (admission to the first token, fenced; ``tokens`` = prompt tokens
  actually prefilled) and ``serve/req<N>/decode``, on the monotonic
  clock.

The weights are made by the benchmark, from the seed.  What differs
from one model family to another (the sizes the program's config has
to state, the program's parameter tree, the work a token needs) lives
in ``families/<reference>.py``, one file a family.
"""
from __future__ import annotations

from typing import Dict

import dataclasses
import time

import jax
import jax.numpy as jnp

import repro.core as pmt
from repro import configs as registry
from repro.models import model as model_mod
from repro.serve import engine as engine_mod

Request = engine_mod.Request


class Tokens(list):
    """A request's output list that notes, at each append or extend, the
    tokens served so far and the monotonic time: ``times``."""

    def __init__(self, items=()):
        super().__init__(items)
        self.times = [(len(self), time.monotonic())] if self else []

    def append(self, tok):
        super().append(tok)
        self.times.append((len(self), time.monotonic()))

    def extend(self, toks):
        super().extend(toks)
        self.times.append((len(self), time.monotonic()))


@dataclasses.dataclass
class TimedRequest(Request):
    """A ``Request`` whose ``out`` notes when its tokens reach the host."""

    def __setattr__(self, name, value):
        if name == "out":
            value = Tokens(value)
        super().__setattr__(name, value)

# Kernel names as the device trace shows them (the ``pallas_call`` name
# of each kernel), by the step program that runs them.
KERNELS = {"decode_attention": "paged_decode_attention",
           "prefill_attention": "paged_prefill_attention",
           "cache_update": "paged_cache_update"}


def model_config(c: Dict, family):
    """The program's config for benchmark config ``c``, checked against
    the published sizes the file states (``family.stated``)."""
    prog = c["program"]
    cfg = registry.get_config(prog["registry"], **prog.get("overrides", {}))
    want = family.stated(c)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{prog['registry']}: the program's config "
                         f"{got} is not the one stated {want}")
    return cfg


def check_layout(cfg, params) -> None:
    """Fail unless ``params`` has exactly the tree, shapes and dtypes
    that the program's own initializer gives."""
    want = jax.eval_shape(
        lambda: model_mod.init_params(jax.random.PRNGKey(0), cfg)[0])
    sd = lambda t: jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), t)
    if sd(want) != sd(params):
        raise ValueError("the weights' layout is not the program's: "
                         f"{sd(params)} != {sd(want)}")


def make_session():
    session = pmt.Session(["tpu"])
    exporter = session.add_exporter(pmt.MemoryExporter())
    return session, exporter


def make_engine(cfg, params, engine: Dict, session):
    """The engine as operators run it: paged KV, chunked prefill at the
    config's chunk, greedy, the radix prefix cache on."""
    return engine_mod.ServeEngine(
        cfg, params, batch_size=engine["slots"], max_len=engine["max_len"],
        session=session, kv_layout="paged",
        kv_page_size=engine["page_size"], kv_pool_pages=engine["pool_pages"],
        prefill_chunk=cfg.prefill_chunk, prefix_cache=True, greedy=True,
        cache_dtype="bfloat16")
