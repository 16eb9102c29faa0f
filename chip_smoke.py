"""Chip smoke test: serve qwen3-0.6b at full published width on one TPU.

Run from the root of a checkout, on a machine with one TPU:

    python3 chip_smoke.py

It drives the normal serve path — ``ServeEngine`` with the default
"auto" kernel dispatch — once per KV layout (contiguous, then paged,
both with bf16 caches) on 8 requests whose prompts span several 32-token
prefill chunks and several KV blocks, each asking for 32 new tokens.
Weights are random, drawn from a fixed seed.  Each layout runs three
phases, each a function a CPU test rehearses at reduced size:

  * ``kernel_phase`` — the compiled prefill and decode steps must hold a
    ``tpu_custom_call`` for every Pallas kernel family that step uses;
    a ``PMT_*`` dispatch variable or an "auto" that sent the chip down
    a lax twin fails here.
  * ``logit_phase`` — first-token logits of the same prompts through the
    Pallas dispatch and through the lax dispatch must agree within
    ``LOGIT_BOUND`` (see its comment).
  * ``serve_phase`` — every request must finish by length; each one's
    joules come from a ``pmt.Session`` on the modelled ``tpu`` backend
    alone (no dummy sensor behind it) and are printed as ``modeled``.

Everything runs in this one process, which holds the chip.  Any failure
exits non-zero before the last line, which on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Off a TPU (``JAX_PLATFORMS=cpu``, or no chip) it exits 1 and names the
platform it found.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import pathlib
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import repro.core as pmt  # noqa: E402
from repro import configs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402

ARCH = "qwen3-0.6b"
BATCH = 8
MAX_LEN = 1024
MAX_NEW = 32
N_REQUESTS = 8
PROMPT_LENS = (100, 600)        # inclusive range of prompt lengths
SEED = 0
LAYOUTS = ("contiguous", "paged")

# Pallas kernel families each compiled serve step must contain (the
# kernels' ``pallas_call`` names).  Contiguous prefill writes its chunk
# rows with a masked lax update, so it has no cache-update kernel.
KERNELS = {
    ("contiguous", "prefill"): {"prefill_attention"},
    ("contiguous", "decode"): {"decode_attention", "cache_update"},
    ("paged", "prefill"): {"paged_prefill_attention", "paged_cache_update"},
    ("paged", "decode"): {"paged_decode_attention", "paged_cache_update"},
}

# The dispatch variables the kernel ops layers read when they trace.
LAX_DISPATCH = {"PMT_PREFILL_ATTENTION_DISPATCH": "lax",
                "PMT_DECODE_ATTENTION_DISPATCH": "lax",
                "PMT_CACHE_UPDATE_IMPL": "lax"}

# Bound on max |logit_pallas - logit_lax| over max |logit_lax|.  The lax
# twin is another algorithm than the kernel (one dense softmax over
# [prefix ++ chunk] against a blockwise online softmax), and the TPU
# runs the f32 dots of both in one bf16 pass, so their attention
# outputs differ at bf16 resolution (a relative step of 2^-8 ~ 0.4%);
# every layer then rounds its activations to bf16, which turns those
# differences into whole steps that carry through the later layers.
# On a TPU v5e, qwen3-0.6b with seed-0 weights measures 1.0e-2 at one
# layer and 1.6e-2 at all 28, while each kernel matches its blockwise
# twin in ``ref.py`` to f32 rounding.  A wrong mask, block or page moves
# the attention of the rows it hits by its own order of magnitude.
LOGIT_BOUND = 0.02

OK_FINISH = ("length",)


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def make_prompts(cfg, n: int = N_REQUESTS, lens=PROMPT_LENS,
                 seed: int = SEED):
    """``n`` random prompts with lengths drawn from ``lens``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(rng.integers(
        lens[0], lens[1] + 1))).tolist() for _ in range(n)]


def init_params(cfg, seed: int = SEED):
    """Random weights from ``seed``, built on the device in one program."""
    return jax.jit(lambda k: model_mod.init_params(k, cfg)[0])(
        jax.random.PRNGKey(seed))


@contextlib.contextmanager
def lax_dispatch():
    """Trace with every serve kernel family on its lax twin."""
    saved = {k: os.environ.get(k) for k in LAX_DISPATCH}
    os.environ.update(LAX_DISPATCH)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def kernel_families(hlo_text: str) -> set:
    """Names of the Pallas kernels a compiled program calls on the TPU."""
    return {m.group(1) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)]
            if m}


def check_kernels(hlo_text: str, layout: str, step: str) -> set:
    """Fail unless the compiled ``step`` holds every expected family."""
    found = kernel_families(hlo_text)
    missing = KERNELS[(layout, step)] - found
    if missing:
        raise SmokeFailure(
            f"{layout} {step} step runs no Pallas kernel for "
            f"{sorted(missing)} (found {sorted(found)}): the dispatch "
            f"took a lax twin")
    return found


def _paged_table(n_rows: int, max_len: int, page_size: int) -> np.ndarray:
    """Row ``i`` owns pages ``1 + i*npp ...`` (page 0 is scratch)."""
    npp = math.ceil(max_len / page_size)
    return (1 + np.arange(n_rows * npp, dtype=np.int32)).reshape(n_rows, npp)


def first_token_logits(cfg, params, prompts, layout: str, *,
                       max_len: int = MAX_LEN):
    """First-token logits (N, V) float32 of each prompt after chunked
    prefill through ``layout``'s serve fns, with the dispatch the
    environment selects when they trace; also the compiled text of the
    prefill-chunk step."""
    t = cfg.prefill_chunk
    if layout == "contiguous":
        step = jax.jit(model_mod.make_serve_fns(cfg).prefill_chunk,
                       donate_argnums=1)
        compiled, rows = None, []
        for p in prompts:
            n_chunks = math.ceil(len(p) / t)
            toks = np.zeros((1, n_chunks * t), np.int32)
            toks[0, :len(p)] = p
            caches = model_mod.init_caches(cfg, 1, max_len)
            for c in range(n_chunks):
                args = (params, caches, jnp.asarray(toks[:, c * t:(c + 1) * t]),
                        jnp.asarray(c * t, jnp.int32),
                        jnp.asarray(min(len(p) - 1 - c * t, t - 1), jnp.int32))
                if compiled is None:
                    compiled = step.lower(*args).compile()
                logits, caches = compiled(*args)
            rows.append(np.asarray(logits[0], np.float32))
        return np.stack(rows), compiled.as_text()

    ps = cfg.kv_page_size
    n = len(prompts)
    table = jnp.asarray(_paged_table(n, max_len, ps))
    caches = model_mod.init_paged_caches(cfg, 1 + table.size, ps)
    n_chunks = [math.ceil(len(p) / t) for p in prompts]
    toks = np.zeros((n, max(n_chunks) * t), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    step = jax.jit(model_mod.make_paged_serve_fns(cfg).prefill_chunk,
                   donate_argnums=1)
    compiled, out = None, [None] * n
    for c in range(max(n_chunks)):
        # rows past their final chunk ride along as passengers (-1)
        last = np.array([-1 if c >= k else min(len(p) - 1 - c * t, t - 1)
                         for p, k in zip(prompts, n_chunks)], np.int32)
        args = (params, caches, jnp.asarray(toks[:, c * t:(c + 1) * t]),
                jnp.full((n,), c * t, jnp.int32), jnp.asarray(last), table)
        if compiled is None:
            compiled = step.lower(*args).compile()
        logits, caches = compiled(*args)
        for i, k in enumerate(n_chunks):
            if c == k - 1:
                out[i] = np.asarray(logits[i], np.float32)
    return np.stack(out), compiled.as_text()


def logit_phase(cfg, params, prompts, layout: str, *,
                max_len: int = MAX_LEN):
    """Pallas-vs-lax first-token logits.  Returns (relative gap, argmax
    agreements, compiled Pallas prefill text); fails past the bound."""
    got, text = first_token_logits(cfg, params, prompts, layout,
                                   max_len=max_len)
    with lax_dispatch():
        want, lax_text = first_token_logits(cfg, params, prompts, layout,
                                            max_len=max_len)
    if kernel_families(lax_text):
        raise SmokeFailure(f"{layout}: the lax dispatch still ran "
                           f"{sorted(kernel_families(lax_text))}")
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        raise SmokeFailure(f"{layout}: non-finite first-token logits")
    scale = float(np.max(np.abs(want)))
    gap = float(np.max(np.abs(got - want))) / max(scale, 1e-30)
    agree = int(np.sum(np.argmax(got, -1) == np.argmax(want, -1)))
    if gap > LOGIT_BOUND:
        raise SmokeFailure(
            f"{layout}: Pallas and lax first-token logits differ by "
            f"{gap:.3e} of max |logit| {scale:.3f} (bound {LOGIT_BOUND})")
    return gap, agree, text


def kernel_phase(cfg, params, layout: str, *, batch: int = BATCH,
                 max_len: int = MAX_LEN) -> str:
    """Compiled text of ``layout``'s decode step at the engine's shapes,
    traced with the dispatch the environment selects."""
    i32 = jax.ShapeDtypeStruct((batch,), jnp.int32)
    toks = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    if layout == "contiguous":
        caches = jax.eval_shape(
            lambda: model_mod.init_caches(cfg, batch, max_len))
        step = jax.jit(engine_mod.make_decode_fn(cfg))
        lowered = step.lower(params, caches, toks, i32)
    else:
        table = _paged_table(batch, max_len, cfg.kv_page_size)
        caches = jax.eval_shape(lambda: model_mod.init_paged_caches(
            cfg, 1 + table.size, cfg.kv_page_size))
        step = jax.jit(engine_mod.make_paged_decode_fn(cfg),
                       donate_argnums=1)
        lowered = step.lower(params, caches, toks, i32,
                             jax.ShapeDtypeStruct(table.shape, jnp.int32))
    return lowered.compile().as_text()


def serve_phase(cfg, params, prompts, layout: str, *, batch: int = BATCH,
                max_len: int = MAX_LEN, max_new: int = MAX_NEW) -> dict:
    """Serve ``prompts`` through ``ServeEngine`` with a session on the
    modelled ``tpu`` backend.  A one-request warm-up compiles every step
    first, so the timed run compiles nothing."""
    session = pmt.Session(["tpu"])
    try:
        sensors = session.sensors
        if [s.name for s in sensors] != ["tpu"] or sensors[0].kind != \
                "modeled":
            raise SmokeFailure(f"session sensors {sensors}: want the "
                               f"modelled tpu backend alone")
        energy = session.add_exporter(pmt.MemoryExporter())
        engine = engine_mod.ServeEngine(cfg, params, batch_size=batch,
                                        max_len=max_len, session=session,
                                        kv_layout=layout)
        t0 = time.perf_counter()
        warm = engine.generate([engine_mod.Request(
            prompt=prompts[0][:cfg.prefill_chunk + 1], max_new_tokens=2)])
        compile_s = time.perf_counter() - t0
        counts = dict(engine.compile_counts)
        reqs = [engine_mod.Request(prompt=p, max_new_tokens=max_new)
                for p in prompts]
        t0 = time.perf_counter()
        done = engine.generate(reqs)
        serve_s = time.perf_counter() - t0
        if engine.compile_counts != counts:
            raise SmokeFailure(f"{layout}: timed run recompiled "
                               f"({counts} -> {engine.compile_counts})")
        session.flush()
        joules = {r.path: r for r in energy.records
                  if r.path.startswith("serve/req") and r.path.count("/") == 1}
        per_req = []
        for r in warm + done:
            rec = joules.get(f"serve/req{r.id}")
            if rec is None or rec.sensor != "tpu" or rec.kind != "modeled":
                raise SmokeFailure(f"{layout}: request {r.id} has no "
                                   f"modelled tpu energy record ({rec})")
            per_req.append((r, rec.joules))
        bad = [(r.id, r.finish_reason) for r in warm + done
               if r.finish_reason not in OK_FINISH
               or len(r.out) != r.max_new_tokens]
        if bad:
            raise SmokeFailure(f"{layout}: requests finished badly: {bad}")
        return dict(compile_s=compile_s, serve_s=serve_s,
                    tokens=sum(len(r.out) for r in done),
                    requests=per_req[1:],
                    stats=engine.stats())
    finally:
        session.close()


def run_layout(cfg, params, prompts, layout: str) -> None:
    """All three phases of one layout, printing what they measured."""
    t0 = time.perf_counter()
    found = check_kernels(kernel_phase(cfg, params, layout), layout, "decode")
    gap, agree, text = logit_phase(cfg, params, prompts, layout)
    found |= check_kernels(text, layout, "prefill")
    print(f"[{layout}] kernels: {sorted(found)}; logit check: "
          f"{gap:.3e} of max |logit| (bound {LOGIT_BOUND}), argmax agrees "
          f"on {agree}/{len(prompts)}; check compiles+runs "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    res = serve_phase(cfg, params, prompts, layout)
    print(f"[{layout}] compile (warm-up) {res['compile_s']:.1f} s; served "
          f"{res['tokens']} tokens in {res['serve_s']:.3f} s "
          f"({res['tokens'] / res['serve_s']:.1f} tokens/s)", flush=True)
    for r, j in res["requests"]:
        print(f"[{layout}]   req{r.id}: prompt {len(r.prompt)} tokens, "
              f"{len(r.out)} out, finish {r.finish_reason}, "
              f"{j:.4f} J modeled", flush=True)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    cfg = configs.get_config(ARCH)
    print(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} kv "
          f"heads x {cfg.head_dim}, vocab {cfg.vocab_size}; batch {BATCH}, "
          f"max_len {MAX_LEN}, {N_REQUESTS} requests x {MAX_NEW} new "
          f"tokens, prefill chunk {cfg.prefill_chunk}, page "
          f"{cfg.kv_page_size}", flush=True)
    print(f"device {dev.device_kind} x {len(jax.devices())} "
          f"({dev.platform}); compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg))
    print(f"params: {sum(x.size for x in jax.tree.leaves(params)):,} "
          f"random weights in {time.perf_counter() - t0:.1f} s", flush=True)
    prompts = make_prompts(cfg)
    try:
        for layout in LAYOUTS:
            run_layout(cfg, params, prompts, layout)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
