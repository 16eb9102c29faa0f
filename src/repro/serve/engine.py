"""Serving: prefill/decode step functions + a continuous-batching engine.

``make_prefill_fn`` / ``make_decode_fn`` / ``make_prefill_chunk_fn`` are
the pjit-able pure steps the dry-run lowers (``serve_step`` for the
decode_* shapes = one new token against a seq_len cache).

``ServeEngine`` implements **sequence-level continuous batching**
(``mode="continuous"``, the default): every batch slot carries its own
position counter, one decode step advances all live slots at their own
offsets (per-row KV-cache scatter via ``kernels/cache_update`` — Pallas
on TPU, ``vmap``'d dynamic-update-slice elsewhere), and a slot that
finishes its request is refilled from the queue on the *next* step
instead of idling until the longest request in a synchronized wave
drains.

Admission is **chunked prefill interleaved with decode** (the
``prefill_chunk`` knob, default ``cfg.prefill_chunk``): a request's
prompt is processed ``prefill_chunk`` tokens at a time through
``ServeFns.prefill_chunk`` — each chunk attends the request's already-
written cache prefix plus its own causal keys via the
``kernels/prefill_attention`` flash kernel and scatters its KV slice in
place — and the scheduler drains the chunk queue *alongside* decode,
one chunk per decode step.  Two levers fall out:

  * prefill compiles **once**, at one (1, chunk) shape, for any prompt
    length — no power-of-two bucket family, and pad waste shrinks from
    up-to-2x (bucketing) to the final partial chunk;
  * a whole-prompt admission no longer stalls the live decode batch:
    the head-of-line decode stall per admission drops from a full
    prompt's prefill to one chunk (see benchmarks/bench_prefill.py;
    per-generate stall samples are kept in ``stall_events``).

``prefill_chunk=0`` keeps the previous *blocking bucketed* admission —
one whole-prompt prefill per request at a power-of-two prompt bucket —
as the measured baseline (and the fallback for encoder-decoder archs,
whose cross-attention KV needs one whole-encoder pass).  Note the
semantic difference: bucketed prefill left-pads the prompt (pad tokens
sit *in context* at the sequence start and shift RoPE positions), while
chunked prefill processes the exact prompt from position 0 — for
prompts that are not already bucket-sized the two can generate
different tokens, chunked being the faithful one.  ``mode="wave"``
keeps the old synchronized-wave decode as the coarser baseline (see
benchmarks/bench_serve.py).

Sampling: ``ServeEngine(greedy=False, temperature=..., seed=...)``
threads a per-step PRNG key (``fold_in`` of a seeded base key and a
monotone step counter) into ``make_decode_fn``'s categorical draw —
and into the prefill fns for the first token — instead of always
decoding greedily.

PMT integration — per-request, per-phase energy attribution: each
admitted request opens a flat session span (``serve/req<N>``,
``nested=False`` so interleaved lifetimes don't fight the nesting
stack) closed right after the fenced decode step that produced its
last token, plus two *phase* child scopes tiling the same window:
``serve/req<N>/prefill`` (admission -> last prefill chunk fenced,
token count = prompt length) and ``serve/req<N>/decode`` (first ->
last decode token, token count = generated tokens).  All spans resolve
in vectorized batches against the shared background ring sampler, so
the engine reports true per-request J/token — split by phase — next to
the aggregate region (``serve/batch<N>`` / ``serve/wave<N>``) whose
token count is the *actually generated* total.  Passing a
``PowerMonitor`` routes the same spans through
``measure_step``/``measure_request(..., phase=...)`` accounting
instead (``per_request_energy`` then carries the J split).
``stats()["measurement"]`` (with a session) is the plane's own host
cost: ``Session.stats()``'s region, sampler and resolver counters.

Profiler annotations — for operators reading a ``jax.profiler`` trace
(always on; about a microsecond each with the profiler off).  Every
request span above, with or without a session or monitor, has a
``TraceAnnotation`` twin of the same path (``serve/req<N>``,
``serve/req<N>/prefill``, ``serve/req<N>/decode``), opened and closed
with it, so each measured span finds its place on the device timeline
by path.  The paged scheduler loop runs each part of an iteration under
one phase annotation; the phases tile the loop body:

  * ``engine/wait`` — asleep until an arrival or the end of a backoff;
  * ``engine/admit`` — drain check, deadline sweep, gauges, governor
    shed, admission (radix match, page reservation, span opening);
  * ``engine/prefill`` — build and dispatch one batched chunk, then
    complete and activate the rows it finished; ``engine/prefill/fetch``
    inside it is the host waiting on the chunk's tokens;
  * ``engine/decode`` — mask the page table and dispatch the burst of
    steps; ``engine/decode/fetch`` inside it waits on the burst's
    tokens;
  * ``engine/retire`` — extend, quarantine, retire and radix-insert the
    burst's rows.

The step programs are named ``serve_decode``, ``serve_prefill_chunk``
and ``serve_prefill`` (``jit_serve_decode`` in a trace).

Known semantic caveat: MoE layers route with cross-batch capacity
limits, so under continuous batching a request's tokens can be dropped
differently depending on its slot neighbours; dense/GQA/MLA/SSM archs
decode each row independently (slot refill leaks no state — see
tests/test_serve_continuous.py for the byte-parity gate).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import os
import time
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.serve.paging import SCRATCH_PAGE, PagePool, RadixPrefixCache


def _pick(logits, greedy: bool, temperature: float, key):
    if greedy or key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def make_prefill_fn(cfg: ModelConfig, max_len: int, greedy: bool = True,
                    temperature: float = 1.0, cache_dtype=jnp.bfloat16):
    prefill = model_mod.make_serve_fns(cfg, cache_dtype=cache_dtype).prefill

    def prefill_fn(params, batch, key=None):
        logits, caches = prefill(params, batch, max_len)
        return _pick(logits, greedy, temperature, key), caches

    return prefill_fn


def make_prefill_chunk_fn(cfg: ModelConfig, greedy: bool = True,
                          temperature: float = 1.0):
    """One prefill chunk: resume the cache at ``offset``, return the
    token sampled from the ``last_idx`` position's logits (only the
    final chunk's is used) plus the updated caches."""
    prefill_chunk = model_mod.make_serve_fns(cfg).prefill_chunk

    def chunk_fn(params, caches, tokens, offset, last_idx, key=None):
        logits, caches = prefill_chunk(params, caches, tokens, offset,
                                       last_idx)
        return _pick(logits, greedy, temperature, key), caches

    return chunk_fn


def make_decode_fn(cfg: ModelConfig, greedy: bool = True,
                   temperature: float = 1.0):
    decode = model_mod.make_serve_fns(cfg).decode

    def decode_fn(params, caches, tokens, cur_len, key=None):
        logits, caches = decode(params, caches, tokens, cur_len)
        return _pick(logits, greedy, temperature, key)[:, None], caches

    return decode_fn


def make_paged_decode_fn(cfg: ModelConfig, greedy: bool = True,
                         temperature: float = 1.0):
    """Paged decode step.  Returns ``(tokens (B,1), ok (B,), caches)``
    where ``ok[j]`` is True iff row ``j``'s logits were all finite —
    the engine's NaN/Inf quarantine reads it for the rows it actually
    consumes (dead/masked rows attend the scratch page and may be
    legitimately garbage)."""
    decode = model_mod.make_paged_serve_fns(cfg).decode

    def decode_fn(params, caches, tokens, cur_len, page_table, key=None):
        logits, caches = decode(params, caches, tokens, cur_len, page_table)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        return _pick(logits, greedy, temperature, key)[:, None], ok, caches

    return decode_fn


def make_paged_prefill_chunk_fn(cfg: ModelConfig, greedy: bool = True,
                                temperature: float = 1.0):
    """Batched paged prefill chunk: every pending admission's next chunk
    rides in one (B, chunk) dispatch, each row at its own offset with
    its own fill (``last_idx[j] == -1`` marks passenger rows).  Returns
    ``(tokens (B,), ok (B,), caches)`` — only rows finishing their
    prefill this step use their token, and ``ok`` flags finite logits
    per row for the quarantine check (passenger rows' flags are
    meaningless and ignored)."""
    pf = model_mod.make_paged_serve_fns(cfg).prefill_chunk

    def chunk_fn(params, caches, tokens, offset, last_idx, page_table,
                 key=None):
        logits, caches = pf(params, caches, tokens, offset, last_idx,
                            page_table)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        return _pick(logits, greedy, temperature, key), ok, caches

    return chunk_fn


def prompt_bucket(plen: int, min_bucket: int = 8) -> int:
    """Pad a prompt length to its power-of-two bucket.

    Bounds the *blocking* prefill jit cache: every prompt length in
    (2^(k-1), 2^k] shares one compiled prefill, so at most
    log2(max_len) prefill variants exist no matter how many distinct
    lengths arrive.  Used by the wave baseline and the
    ``prefill_chunk=0`` blocking admission; chunked admission compiles
    one shape and needs no buckets.

    ``min_bucket`` must itself be a power of two — a non-power floor
    would silently produce non-power buckets (``b <<= 1`` preserves
    whatever factor it starts with) and fracture the jit cache.
    """
    if plen < 1:
        raise ValueError("empty prompt")
    if min_bucket < 1 or (min_bucket & (min_bucket - 1)):
        raise ValueError(
            f"min_bucket must be a power of two >= 1, got {min_bucket}")
    b = min_bucket
    while b < plen:
        b <<= 1
    return b


def stall_p95(events) -> float:
    """p95 of the engine's ``stall_events`` samples (nearest-rank on the
    inclusive index) — shared by the serve launcher and
    benchmarks/bench_prefill.py so the two report the same number."""
    if not events:
        return 0.0
    xs = sorted(events)
    return float(xs[min(len(xs) - 1, int(round(0.95 * (len(xs) - 1))))])


def resolve_prefill_chunk(cfg: ModelConfig,
                          prefill_chunk: Optional[int]) -> int:
    """Engine arg beats the ``PMT_PREFILL_CHUNK`` env var beats
    ``cfg.prefill_chunk``; encoder-decoder archs force 0 (blocking)."""
    if prefill_chunk is None:
        env = os.environ.get("PMT_PREFILL_CHUNK")
        prefill_chunk = int(env) if env else cfg.prefill_chunk
        if cfg.is_encoder_decoder:
            prefill_chunk = 0
    if prefill_chunk < 0:
        raise ValueError(f"prefill_chunk must be >= 0, got {prefill_chunk}")
    if prefill_chunk and cfg.is_encoder_decoder:
        raise ValueError(
            "chunked prefill is not available for encoder-decoder archs "
            "(cross-attention KV needs one whole-encoder pass); use "
            "prefill_chunk=0")
    return prefill_chunk


@dataclasses.dataclass
class Request:
    """One serve request: prompt in, ``out`` tokens back.

    ``finish_reason`` vocabulary (None until served — or *still* None
    after a ``drain()`` checkpointed the request mid-flight, in which
    case re-submitting it to ``generate()`` resumes where it left off):

      * ``"length"`` — ran to ``max_new_tokens`` (the normal case).
      * ``"timeout"`` — blew past ``deadline_s`` (waiting, mid-prefill,
        mid-decode, *or while parked after a preemption*); keeps the
        tokens generated so far.  Timeout outranks retry: a preempted
        request whose deadline expires in the waiting queue retires
        ``"timeout"``, not preempted-*.
      * ``"preempted-retry-exhausted"`` — preempted more than the
        engine's ``preempt_max_retries`` times under sustained pool
        pressure; keeps the tokens generated before the final
        preemption.
      * ``"error"`` — the decode/prefill step that would have produced
        this request's next token emitted non-finite (NaN/Inf) logits;
        the engine quarantines the request (pages dropped, never
        adopted into the prefix tree) instead of letting poison spread
        through the shared batch.  With ``retry_errors=True`` the
        engine first retries it from scratch with backoff.

    Preemption prices its own waste: every token a preempted request
    must *re-run* at resume (prompt + generated tokens already computed
    once, minus whatever the radix tree or swap store restored) accrues
    to the engine's ``wasted_joules`` at the learned J/prefill-token
    EWMA — recompute energy that an unpressured run would not have
    spent.  Tokens restored copy-free (tree hit) or via host swap
    accrue to ``recovered_joules`` instead: energy the resume path
    *saved* relative to recomputing everything.  Both surface in
    ``engine.stats()["preemption"]``.
    """

    prompt: Sequence[int]
    max_new_tokens: int
    out: List[int] = dataclasses.field(default_factory=list)
    id: Optional[int] = None        # assigned by the engine at admission
    tenant: Optional[str] = None    # quota accounting key (governor)
    # Wall-clock budget in seconds, measured from generate() submission
    # (continuous mode only).  A request past its deadline — waiting,
    # mid-prefill, or mid-decode — retires with finish_reason "timeout",
    # keeps whatever tokens it generated, closes its spans cleanly, and
    # frees its slot.  None = no deadline.
    deadline_s: Optional[float] = None
    # See the class docstring for the full vocabulary.
    finish_reason: Optional[str] = None
    # Preemption victim ordering: lower priority is preempted first
    # (ties broken by least progress — least recompute waste).
    priority: int = 0
    # -- engine-managed resume state (opaque to callers) --------------
    _preempts: int = 0                      # times preempted so far
    _retry_at: float = 0.0                  # monotonic re-admission gate
    _progress: int = 0                      # tokens computed at checkpoint
    _swap_handle: Optional[int] = None      # PageSwapStore entry
    _swap_pages: int = 0


class _Traced:
    """A measurement context together with a profiler annotation of the
    same path, opened and closed at the same points: the annotation
    places the span on the device trace's clock."""

    __slots__ = ("_ctx", "_ann")

    def __init__(self, path: str, ctx):
        self._ctx = ctx
        self._ann = jax.profiler.TraceAnnotation(path)

    def __enter__(self):
        out = self._ctx.__enter__()
        self._ann.__enter__()
        return out

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return self._ctx.__exit__(*exc)


class _Phases:
    """The scheduler loop's phase as a profiler annotation.  Each call
    closes the phase before and opens the next, so the phases tile the
    loop body; ``end()`` closes the last."""

    __slots__ = ("_ann",)

    def __init__(self):
        self._ann = None

    def __call__(self, name: str) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()

    def end(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


@dataclasses.dataclass
class _Prefill:
    """An admission mid-chunked-prefill: its slot is reserved, its
    batch-1 cache row is being built chunk by chunk.  The open
    serve/req<N>/prefill span lives in the engine loop's per-slot
    ``pf_ctxs`` (closed on completion or by the cleanup ``finally``)."""

    req: Request
    slot: int
    caches: Any                     # batch-1 cache tree under construction
    toks: np.ndarray                # (1, padded) right-padded prompt
    plen: int
    offset: int = 0


@dataclasses.dataclass
class _PagedPrefill:
    """An admission mid-chunked-prefill on the *paged* path: its slot
    and pages are reserved; chunks write straight into the shared pools
    through the slot's page-table row (no batch-1 side cache, no insert
    step).  ``offset`` starts at ``matched_tokens`` when the radix
    prefix cache mapped cached pages in — prefill resumes from the
    match point."""

    req: Request
    slot: int
    toks: np.ndarray                # (plen + chunk,) right-zero-padded
    plen: int
    offset: int
    matched_tokens: int = 0
    # (host leaf arrays, n_pages) swap snapshot awaiting scatter into
    # the freshly-allocated pages (resume-from-swap admissions only).
    swap_restore: Optional[Tuple[List[np.ndarray], int]] = None


class ServeEngine:
    """Continuous-batching decode over fixed slots (wave mode as baseline).

    Args:
      cfg, params: model config + parameter tree.
      batch_size: number of decode slots.
      max_len: KV-cache capacity per slot.  Chunked admission needs
        ``ceil(plen / chunk) * chunk <= max_len`` and
        ``plen + max_new_tokens <= max_len + 1``; blocking/wave
        admission needs ``prompt_bucket(plen) + max_new_tokens
        <= max_len + 1``.
      monitor: a ``PowerMonitor`` — aggregate regions go through its
        non-blocking ``measure_step``, per-request and per-phase spans
        through ``measure_request(..., phase=...)`` (J/token and the
        prefill/decode J split per request via
        ``monitor.per_request_energy()``).
      session: a ``pmt.Session`` — aggregate region ``serve/batch<N>``
        (or ``serve/wave<N>``) plus flat ``serve/req<N>`` /
        ``serve/req<N>/prefill`` / ``serve/req<N>/decode`` spans per
        request, all resolved asynchronously off the shared ring
        sampler.  Monitor wins when both are passed.
      mode: "continuous" (default) or "wave" (synchronized baseline).
      min_prompt_bucket: smallest prompt bucket (power of two; blocking
        and wave admission only).
      cache_impl: per-row scatter impl forwarded to
        ``kernels/cache_update`` ("auto" picks Pallas on TPU).
      decode_attn_impl: overrides ``cfg.decode_attn_impl`` for this
        engine — "flash" routes decode attention through the
        length-aware ``kernels/decode_attention`` path, "dense" keeps
        the masked full-cache attend, "auto" picks flash on TPU.
      prefill_chunk: chunk size for interleaved chunked prefill; 0 =
        blocking bucketed admission (the measured baseline); None
        (default) resolves ``PMT_PREFILL_CHUNK`` then
        ``cfg.prefill_chunk``.
      governor: a ``serve.governor.PowerGovernor`` consulted by the
        continuous scheduler at admission (gate + tenant-priority pick),
        chunk drain (0..max chunks per decode step), and before each
        decode dispatch (duty-cycle pause) — holds the engine under the
        governor's watts cap / tenant quotas.  With a cap set, decode
        runs one step per loop so the governor sees every step;
        ``cap_watts=None`` keeps the bursty device-side decode runs.
        Ignored in wave mode (the synchronized baseline has no
        per-step scheduling points to govern).
      kv_layout: "contiguous" (default) keeps per-slot (B, max_len, ...)
        caches; "paged" serves from one physical page pool per cache
        leaf with per-slot page tables — pages are allocated at
        admission (after a radix prefix-cache match maps any cached
        prompt prefix in copy-free) and recycled at retirement, so the
        cache-memory budget is the *pool*, decoupled from slots x
        max_len.  Requires continuous mode, chunked prefill, and an
        all-attention arch (``model.supports_paged``).
      kv_page_size: tokens per page (default ``cfg.kv_page_size``).
      kv_pool_pages: usable pool capacity in pages (default
        ``batch_size * ceil(max_len / page_size)`` — parity with the
        contiguous footprint; smaller pools oversubscribe slots and
        admissions wait for pages).
      prefix_cache: keep retired requests' full prompt pages in a radix
        tree for copy-free prefix reuse (paged layout only).
      preempt: enable preemption-and-resume (paged layout only).  When
        the pool cannot cover the next admission even after radix
        eviction (or the governor requests shedding), the engine
        preempts the lowest-priority live request page-aligned — its
        written pages go to the host swap store when one is configured
        (frees device pages outright, guarantees a restore), else to
        the radix tree (copy-free, but the pages stay pool-resident and
        LRU-evictable — a later miss means honest recompute) — and the
        request rejoins the waiting queue with a saved resume point.
        Off by default: an unpressured engine behaves exactly as before
        (admissions wait on pages).
      preempt_max_retries: preemptions a single request tolerates
        before retiring ``"preempted-retry-exhausted"``.
      retry_backoff_s: base re-admission delay after a preemption
        (doubles per preemption of that request); 0 retries eagerly.
      retry_errors: retry quarantined (non-finite-logit) requests from
        scratch — with the same backoff/retry budget — instead of
        retiring them ``"error"`` on first occurrence.
      swap_store: a ``serve.swap.PageSwapStore`` for preempted pages
        (paged layout only); None = tree-or-recompute only.
      watchdog_step_timeout_s: per-step wall-clock deadline — a fenced
        prefill/decode dispatch exceeding it (per decode step, for
        multi-step bursts) counts a ``hung_steps`` detection and emits
        a telemetry event.  Detection only: a wedged device dispatch
        cannot be aborted from the host, but it must not be *silent*.
      greedy, temperature, seed: decoding policy.  ``greedy=False``
        threads ``fold_in(PRNGKey(seed), step)`` into every decode
        step's categorical draw (and the prefill first-token pick);
        the step counter is monotone across ``generate()`` calls.

    ``compile_counts`` tracks retraces — continuous-mode decode
    compiles exactly once, chunked prefill exactly once (one chunk
    shape), blocking prefill once per prompt bucket.
    ``stall_events`` holds, for the most recent ``generate()``, the
    seconds decode sat blocked behind each fenced prefill dispatch
    (one whole prompt when blocking, one chunk when chunked) while at
    least one request was mid-decode — the head-of-line stall the
    chunked scheduler exists to shrink (p95 reported by
    benchmarks/bench_prefill.py).
    """

    def __init__(self, cfg: ModelConfig, params, batch_size: int,
                 max_len: int, monitor=None, session=None,
                 mode: str = "continuous", min_prompt_bucket: int = 8,
                 cache_impl: str = "auto",
                 decode_attn_impl: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 governor=None,
                 kv_layout: str = "contiguous",
                 kv_page_size: Optional[int] = None,
                 kv_pool_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 preempt: bool = False,
                 preempt_max_retries: int = 8,
                 retry_backoff_s: float = 0.0,
                 retry_errors: bool = False,
                 swap_store=None,
                 watchdog_step_timeout_s: Optional[float] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0, cache_dtype=jnp.bfloat16):
        if mode not in ("continuous", "wave"):
            raise ValueError(f"unknown serve mode {mode!r}")
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if decode_attn_impl is not None:
            cfg = dataclasses.replace(cfg,
                                      decode_attn_impl=decode_attn_impl)
        if not greedy and temperature <= 0.0:
            raise ValueError("sampling needs temperature > 0")
        # ``cache_dtype`` accepts a jnp storage dtype, its name, or a
        # quantized-KV mode string ("int8" / "fp8_e4m3"): the quant
        # modes flip ``cfg.kv_quant`` so every serve fn built below
        # traces the quantized cache tree (code leaves + per-row f32
        # scales; the attention kernels dequantize in-register).
        if isinstance(cache_dtype, str):
            if cache_dtype in ("int8", "fp8_e4m3"):
                cfg = dataclasses.replace(cfg, kv_quant=cache_dtype)
                cache_dtype = jnp.bfloat16      # unused by quant leaves
            else:
                named = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                         "float16": jnp.float16}
                if cache_dtype not in named:
                    raise ValueError(
                        f"unknown cache_dtype {cache_dtype!r}; expected a "
                        f"dtype, one of {sorted(named)}, or a KV-quant "
                        f"mode ('int8', 'fp8_e4m3')")
                cache_dtype = named[cache_dtype]
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.monitor = monitor
        self.session = session
        self.mode = mode
        self.min_prompt_bucket = min_prompt_bucket
        self.cache_impl = cache_impl
        self.prefill_chunk = resolve_prefill_chunk(cfg, prefill_chunk)
        if self.prefill_chunk > max_len:
            if prefill_chunk is not None:
                raise ValueError(f"prefill_chunk {self.prefill_chunk} "
                                 f"exceeds max_len {max_len}")
            # config/env default larger than this engine's cache: clamp
            # (one whole-cache chunk) rather than refuse to serve.
            self.prefill_chunk = max_len
        self.governor = governor
        self.greedy = greedy
        self.temperature = temperature
        # Scheduler gauges — plain attribute reads, safe from any thread
        # (e.g. a load-coupled DummySensor watts_fn or a telemetry stats
        # provider sampling engine state mid-run).
        self.live_slots = 0             # decoding + mid-prefill slots
        self.queue_depth = 0            # admitted-nothing-yet backlog
        self.pending_prefill_chunks = 0
        self._key_base = jax.random.PRNGKey(seed)
        self._step_idx = 0          # monotone sampling-step counter
        self._batch_count = 0       # aggregate regions (waves or batches)
        self._request_count = 0
        self.stall_events: List[float] = []
        # Decode bursts' occupancy: steps dispatched, and rows decoding
        # times steps (their ratio over ``batch`` is the batch's fill).
        self.decode_steps = 0
        self.decode_row_steps = 0
        self._timeouts = 0          # requests retired past their deadline
        # rid -> tenant for every admitted request (telemetry's
        # /requests?tenant= filter reads this via attach_engine).
        self.request_tenants: Dict[int, str] = {}
        self.compile_counts: Dict[str, int] = {"prefill": 0, "decode": 0,
                                               "prefill_chunk": 0}
        self.cache_dtype = cache_dtype
        sample_kw = dict(greedy=greedy, temperature=temperature)
        self._prefill = jax.jit(self._counted(
            "prefill", make_prefill_fn(cfg, max_len, cache_dtype=cache_dtype,
                                       **sample_kw)))
        self._decode = jax.jit(self._counted(
            "decode", make_decode_fn(cfg, **sample_kw)))
        if self.prefill_chunk:
            # Donate the row cache: each chunk overwrites its slice in
            # place instead of copying the whole tree per chunk.
            self._prefill_chunk_fn = jax.jit(
                self._counted("prefill_chunk",
                              make_prefill_chunk_fn(cfg, **sample_kw)),
                donate_argnums=1)
        self._insert = self._make_insert()

        # -- paged KV cache (block pools + page tables + prefix reuse) --
        self.kv_layout = kv_layout
        self.kv_page_size = int(kv_page_size if kv_page_size is not None
                                else cfg.kv_page_size)
        self.prefix_hit_tokens = 0          # prompt tokens served off pages
        self.saved_prefill_joules = 0.0     # priced at the learned J/token
        self._prefill_jpt: Optional[float] = None   # EWMA J per prefill tok
        self.pool_wait_events = 0           # admissions deferred on pages
        self._pool_short = False            # mid-wait episode flag
        self._bytes_per_token: Optional[float] = None   # stats() memo
        self._pool: Optional[PagePool] = None
        self._radix: Optional[RadixPrefixCache] = None
        # -- preemption / resume / quarantine / watchdog ----------------
        self.preempt_enabled = bool(preempt)
        self.preempt_max_retries = int(preempt_max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_errors = bool(retry_errors)
        self.swap_store = swap_store
        self.watchdog_step_timeout_s = watchdog_step_timeout_s
        self.preemptions = 0
        self.preempt_resumes = 0
        self.retries_exhausted = 0
        self.wasted_tokens = 0              # previously-computed, re-run
        self.wasted_joules = 0.0            # ... priced at the jpt EWMA
        self.recovered_tokens = 0           # restored copy-free / by swap
        self.recovered_joules = 0.0
        self.quarantined = 0
        self.hung_steps = 0
        self.drains = 0
        # per-event accounting log (the chaos bench reconciles its sum
        # against the stats totals and the recorder's span totals)
        self.preempt_log: List[Dict[str, Any]] = []
        self._drain_requested = False
        # telemetry hook: PowerRecorder.attach_engine binds a callable
        # receiving one dict per preempt/resume/quarantine/hung/drain
        # event (fans out on the SSE stream).
        self.event_sink: Optional[Any] = None
        self._swap_gather_fn = None         # built lazily (paged only)
        self._swap_scatter_fn = None
        if kv_layout != "paged":
            if preempt:
                raise ValueError("preempt=True requires kv_layout='paged'")
            if swap_store is not None:
                raise ValueError("swap_store requires kv_layout='paged'")
            if watchdog_step_timeout_s is not None:
                raise ValueError(
                    "watchdog_step_timeout_s requires kv_layout='paged'")
        if watchdog_step_timeout_s is not None \
                and watchdog_step_timeout_s <= 0:
            raise ValueError("watchdog_step_timeout_s must be > 0")
        if kv_layout == "paged":
            if mode != "continuous":
                raise ValueError("paged KV requires continuous mode")
            if not self.prefill_chunk:
                raise ValueError("paged KV requires chunked prefill "
                                 "(prefill_chunk > 0)")
            if not model_mod.supports_paged(cfg):
                raise ValueError(
                    f"{cfg.name}: paged KV needs an all-attention arch "
                    "(state and encoder-decoder archs keep the contiguous "
                    "layout)")
            ps = self.kv_page_size
            if ps < 1:
                raise ValueError(f"kv_page_size must be >= 1, got {ps}")
            self._pages_per_slot = math.ceil(max_len / ps)
            usable = (int(kv_pool_pages) if kv_pool_pages is not None
                      else batch_size * self._pages_per_slot)
            if usable < self._pages_per_slot:
                raise ValueError(
                    f"kv_pool_pages {usable} cannot hold even one slot "
                    f"({self._pages_per_slot} pages of {ps})")
            # +1: page 0 is the reserved scratch page
            self._pool = PagePool(usable + 1, ps)
            if prefix_cache:
                self._radix = RadixPrefixCache(self._pool)
            self._paged_caches = model_mod.init_paged_caches(
                cfg, usable + 1, ps, dtype=cache_dtype)
            self._page_table = np.zeros(
                (batch_size, self._pages_per_slot), np.int32)
            self._slot_pages: List[List[int]] = \
                [[] for _ in range(batch_size)]
            self._paged_decode = jax.jit(
                self._counted("decode",
                              make_paged_decode_fn(cfg, **sample_kw)),
                donate_argnums=1)
            self._paged_prefill_chunk_fn = jax.jit(
                self._counted("prefill_chunk",
                              make_paged_prefill_chunk_fn(cfg, **sample_kw)),
                donate_argnums=1)

    def _counted(self, name: str, fn):
        """``fn`` counted in ``compile_counts[name]`` at each trace, and
        named ``serve_<name>``: the name its compiled program carries in
        a profile (``jit_serve_decode``), so the step programs are told
        apart by name and not only by the kernels they run."""
        counts = self.compile_counts

        def wrapper(*args, **kwargs):
            counts[name] += 1       # runs at trace time == once per compile
            return fn(*args, **kwargs)

        wrapper.__name__ = wrapper.__qualname__ = f"serve_{name}"
        return wrapper

    def _next_key(self):
        """Per-step PRNG key (None when greedy — the jitted fns then
        trace a single keyless signature)."""
        if self.greedy:
            return None
        key = jax.random.fold_in(self._key_base, self._step_idx)
        self._step_idx += 1
        return key

    # -- cache row insertion ------------------------------------------------
    def _make_insert(self):
        """Jitted ``insert(caches, row, j)`` scattering a single-request
        prefill cache (batch 1) into batch row ``j`` of the live caches.

        Cache leaves put the batch axis at different positions (stacked
        units lead with a "layers" axis), so the per-leaf batch-axis
        index comes from ``cache_logical_axes``.
        """
        axes_tree = model_mod.cache_logical_axes(self.cfg)
        is_axes = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        batch_axes = [ax.index("batch") for ax in
                      jax.tree.leaves(axes_tree, is_leaf=is_axes)]

        def insert(caches, row, j):
            leaves, treedef = jax.tree.flatten(caches)
            row_leaves = jax.tree.leaves(row)
            out = []
            for c, r, ax in zip(leaves, row_leaves, batch_axes):
                starts = [0] * c.ndim
                starts[ax] = j
                out.append(jax.lax.dynamic_update_slice(
                    c, r.astype(c.dtype), tuple(starts)))
            return jax.tree.unflatten(treedef, out)

        # Donate the live caches: admission overwrites one row in place
        # instead of copying the whole KV tree per admitted request (the
        # caller always rebinds `caches = insert(caches, ...)`).
        return jax.jit(insert, donate_argnums=0)

    # -- page swap (preemption's host-memory path) ---------------------------
    def _ensure_swap_fns(self) -> None:
        """Jitted per-leaf page gather/scatter for the swap store.

        Page lists are padded to ``_pages_per_slot`` with the scratch
        page so both fns compile exactly once: gathering scratch copies
        a little garbage, scattering into scratch writes garbage to the
        page whose content is garbage by contract.
        """
        if self._swap_gather_fn is not None:
            return
        axes_tree = model_mod.paged_cache_logical_axes(self.cfg)
        is_axes = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        page_axes = [ax.index("kv_pages") for ax in
                     jax.tree.leaves(axes_tree, is_leaf=is_axes)]

        def gather(caches, pages):
            return [jnp.take(c, pages, axis=ax)
                    for c, ax in zip(jax.tree.leaves(caches), page_axes)]

        def scatter(caches, data, pages):
            leaves, treedef = jax.tree.flatten(caches)
            out = []
            for c, d, ax in zip(leaves, data, page_axes):
                idx = (slice(None),) * ax + (pages,)
                out.append(c.at[idx].set(d.astype(c.dtype)))
            return jax.tree.unflatten(treedef, out)

        self._swap_gather_fn = jax.jit(gather)
        # Donated like every other cache-mutating fn: the caller rebinds.
        self._swap_scatter_fn = jax.jit(scatter, donate_argnums=0)

    def _padded_pages(self, pages: List[int]) -> np.ndarray:
        out = np.full((self._pages_per_slot,), SCRATCH_PAGE, np.int32)
        out[:len(pages)] = pages
        return out

    def _emit_event(self, kind: str, rid: Optional[int], **fields) -> None:
        """Fan one preemption-plane event out to the telemetry sink
        (non-blocking contract: the sink appends and returns)."""
        sink = self.event_sink
        if sink is None:
            return
        ev = {"kind": kind, "rid": rid, "timestamp_s": time.time()}
        ev.update(fields)
        try:
            sink(ev)
        except Exception:
            pass                    # telemetry must never wedge serving

    def _drop_resume_state(self, r: Request) -> None:
        """Discard a request's parked swap entry (it will never resume:
        timeout while waiting, retry budget exhausted, or normal
        retirement after a resume that went through the tree path)."""
        if r._swap_handle is not None and self.swap_store is not None:
            self.swap_store.drop(r._swap_handle)
        r._swap_handle = None
        r._swap_pages = 0

    def drain(self) -> None:
        """Request a graceful drain: at its next scheduler checkpoint
        the engine checkpoints every in-flight request page-aligned
        (pages to swap/tree exactly like a preemption, but charging no
        retry), leaves ``finish_reason`` None on everything unfinished,
        and returns from ``generate()``.  Re-submitting the same
        ``Request`` objects to ``generate()`` resumes them — tree/swap
        hits restore their progress, misses recompute honestly.  Safe
        to call from another thread (one flag write)."""
        self._drain_requested = True

    # -- measurement contexts ----------------------------------------------
    def _measure_ctx(self, agg_id: int, tokens: int):
        # Aggregate region per generate() call (continuous) or per wave.
        # Both paths are non-blocking: exit enqueues a span and returns.
        # Monitor keeps precedence so callers passing both still get its
        # J/token accounting.
        if self.monitor is not None:
            return self.monitor.measure_step(agg_id, tokens=tokens,
                                             blocking=False)
        if self.session is not None:
            label = "wave" if self.mode == "wave" else "batch"
            return self.session.region(f"serve/{label}{agg_id}",
                                       tokens=tokens)
        return contextlib.nullcontext()

    def _request_ctx(self, rid: int, tokens: int,
                     phase: Optional[str] = None):
        label = f"serve/req{rid}" + (f"/{phase}" if phase else "")
        if self.monitor is not None:
            ctx = self.monitor.measure_request(rid, tokens=tokens,
                                               blocking=False, phase=phase)
        elif self.session is not None:
            ctx = self.session.region(label, tokens=tokens, nested=False)
        else:
            ctx = contextlib.nullcontext()
        return _Traced(label, ctx)

    # -- public API ----------------------------------------------------------
    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve ``requests``; returns them in input order, ``out`` filled."""
        chunk = self.prefill_chunk if self.mode == "continuous" else 0
        for r in requests:
            if r.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            if r.deadline_s is not None:
                if r.deadline_s <= 0:
                    raise ValueError(
                        f"deadline_s must be > 0, got {r.deadline_s}")
                if self.mode == "wave":
                    raise ValueError(
                        "deadline_s requires continuous mode (waves have "
                        "no per-request retirement point)")
            if r.finish_reason is not None:
                # A *completed* request re-submitted: serve it fresh.  A
                # request with an id but no finish_reason was drained (or
                # preempted when its run aborted) mid-flight — the paged
                # path resumes it from its checkpoint instead.
                r.id = None
                r.out = []
                r._preempts = 0
                r._progress = 0
                self._drop_resume_state(r)
            r.finish_reason = None
            plen = len(r.prompt)
            if chunk:
                padded = math.ceil(plen / chunk) * chunk
                if padded > self.max_len \
                        or plen + r.max_new_tokens > self.max_len + 1:
                    raise ValueError(
                        f"request needs {max(padded, plen + r.max_new_tokens - 1)} "
                        f"cache slots (chunk-padded prompt / prompt + "
                        f"max_new_tokens) but max_len is {self.max_len}")
            else:
                need = prompt_bucket(plen, self.min_prompt_bucket) \
                    + r.max_new_tokens
                if need > self.max_len + 1:
                    raise ValueError(
                        f"request needs {need} cache slots (bucketed prompt "
                        f"+ max_new_tokens) but max_len is {self.max_len}")
        self.stall_events = []
        if self.governor is not None and self.mode == "continuous":
            self.governor.begin(self)
        if self.mode == "wave":
            done: List[Request] = []
            for i in range(0, len(requests), self.batch):
                wave = requests[i:i + self.batch]
                done.extend(self._run_wave(wave))
            return done
        if self.kv_layout == "paged":
            return self._run_paged(requests)
        return self._run_continuous(requests)

    def stats(self) -> Dict[str, Any]:
        """Scheduler counters snapshot — what the telemetry ``/stats``
        endpoint and the launcher's end-of-run report surface."""
        s: Dict[str, Any] = {
            "mode": self.mode,
            "kv_layout": self.kv_layout,
            "batch_slots": self.batch,
            "requests_admitted": self._request_count,
            "live_slots": self.live_slots,
            "queue_depth": self.queue_depth,
            "pending_prefill_chunks": self.pending_prefill_chunks,
            "stall_events": len(self.stall_events),
            "stall_p95_s": stall_p95(self.stall_events),
            "requests_timed_out": self._timeouts,
            "compile_counts": dict(self.compile_counts),
            "decode_steps": self.decode_steps,
            "decode_row_steps": self.decode_row_steps,
        }
        if self.session is not None:
            s["measurement"] = self.session.stats()
        cache_s: Dict[str, Any] = {
            "cache_dtype": (self.cfg.kv_quant
                            if self.cfg.kv_quant is not None
                            else np.dtype(self.cache_dtype).name),
            "bytes_per_token": self.cache_bytes_per_token(),
        }
        if self._pool is not None:
            cache_s.update(
                page_size=self._pool.page_size,
                pages_total=self._pool.total_pages,
                pages_free=self._pool.free_pages,
                pages_used=self._pool.used_pages,
                pool_wait_events=self.pool_wait_events,
                prefix_cache=self._radix is not None,
                prefix_hit_tokens=self.prefix_hit_tokens,
                saved_prefill_joules=self.saved_prefill_joules)
            if self._radix is not None:
                cache_s.update(
                    prefix_lookups=self._radix.lookups,
                    prefix_hits=self._radix.hits,
                    prefix_hit_rate=self._radix.hit_rate,
                    prefix_evictions=self._radix.evictions,
                    prefix_nodes=self._radix.node_count)
        s["kv_cache"] = cache_s
        pre: Dict[str, Any] = {
            "enabled": self.preempt_enabled,
            "preemptions": self.preemptions,
            "resumes": self.preempt_resumes,
            "retries_exhausted": self.retries_exhausted,
            "wasted_tokens": self.wasted_tokens,
            "wasted_joules": self.wasted_joules,
            "recovered_tokens": self.recovered_tokens,
            "recovered_joules": self.recovered_joules,
            "quarantined": self.quarantined,
            "hung_steps": self.hung_steps,
            "drains": self.drains,
        }
        if self.swap_store is not None:
            pre["swap"] = self.swap_store.stats()
        s["preemption"] = pre
        if self.governor is not None:
            s["governor"] = self.governor.stats()
        return s

    def cache_bytes_per_token(self) -> float:
        """KV-cache bytes per cached token position, all leaves summed —
        the footprint gauge quantized caches exist to shrink (a quant
        mode stores 1-byte codes plus amortized f32 scales instead of
        2-byte bf16 values).  Contiguous: abstract-eval of the cache
        tree over batch x max_len positions.  Paged: live pool leaves
        over pool pages x page_size positions."""
        if self._bytes_per_token is None:
            if self._pool is not None:
                total = sum(l.nbytes
                            for l in jax.tree.leaves(self._paged_caches))
                slots = self._pool.total_pages * self._pool.page_size
            else:
                shapes = jax.eval_shape(
                    lambda: model_mod.init_caches(
                        self.cfg, self.batch, self.max_len,
                        dtype=self.cache_dtype))
                total = sum(math.prod(l.shape) * l.dtype.itemsize
                            for l in jax.tree.leaves(shapes))
                slots = self.batch * self.max_len
            self._bytes_per_token = total / max(1, slots)
        return self._bytes_per_token

    def on_record(self, rec) -> None:
        """Recorder subscriber (wired by ``PowerRecorder.attach_engine``):
        learns joules-per-prefill-token from resolved
        ``serve/req<N>/prefill`` spans — the price of the prefill work a
        prefix-cache hit avoids.  ``saved_prefill_joules`` accrues at
        admission time from this EWMA."""
        path = getattr(rec, "path", "")
        if not (path.startswith("serve/req") and path.endswith("/prefill")):
            return
        tokens = getattr(rec, "tokens", None)
        joules = getattr(rec, "joules", None)
        if not tokens or joules is None or joules <= 0.0:
            return
        jpt = joules / tokens
        self._prefill_jpt = jpt if self._prefill_jpt is None \
            else 0.8 * self._prefill_jpt + 0.2 * jpt

    # -- continuous batching --------------------------------------------------
    def _admit(self, r: Request) -> Request:
        r.id = self._request_count
        self._request_count += 1
        r.out = []
        if r.tenant is not None:
            self.request_tenants[r.id] = r.tenant
        return r

    def _prefill_request(self, r: Request) -> Tuple[np.ndarray, Any, int]:
        """Blocking whole-prompt prefill at the prompt's bucket size
        (the ``prefill_chunk=0`` baseline).

        Returns (first generated token (1,) np.int32, cache row tree
        with batch size 1, next position == bucket size).  Blocking on
        the token fences prefill compute inside the request's span.
        """
        plen = len(r.prompt)
        bucket = prompt_bucket(plen, self.min_prompt_bucket)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, bucket - plen:] = r.prompt          # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.is_encoder_decoder:
            batch["frame_embeds"] = jnp.zeros(
                (1, self.cfg.enc_len, self.cfg.d_model), jnp.bfloat16)
        first, row = self._prefill(self.params, batch, self._next_key())
        return np.asarray(first), row, bucket

    def _start_chunked_prefill(self, r: Request, j: int) -> _Prefill:
        plen = len(r.prompt)
        chunk = self.prefill_chunk
        padded = math.ceil(plen / chunk) * chunk
        toks = np.zeros((1, padded), np.int32)
        toks[0, :plen] = r.prompt                   # right-pad final chunk
        caches = model_mod.init_caches(self.cfg, 1, self.max_len,
                                       dtype=self.cache_dtype)
        return _Prefill(req=r, slot=j, caches=caches, toks=toks, plen=plen)

    def _step_chunked_prefill(self, st: _Prefill, decode_live: bool
                              ) -> Optional[np.ndarray]:
        """Run one chunk; returns the first generated token (1,) when
        this was the final chunk, else None.  Fenced (the chunk's token
        read blocks), so the prefill phase span and the stall sample
        both cover real device work."""
        chunk = self.prefill_chunk
        t0 = time.perf_counter()
        last_idx = min(st.plen - 1 - st.offset, chunk - 1)
        tok, st.caches = self._prefill_chunk_fn(
            self.params, st.caches,
            jnp.asarray(st.toks[:, st.offset:st.offset + chunk]),
            jnp.asarray(st.offset, jnp.int32),
            jnp.asarray(last_idx, jnp.int32), self._next_key())
        tok = np.asarray(tok)                       # fence the chunk
        if decode_live:
            self.stall_events.append(time.perf_counter() - t0)
        st.offset += chunk
        return tok if st.offset >= st.toks.shape[1] else None

    def _run_continuous(self, requests: List[Request]) -> List[Request]:
        b = self.batch
        chunk = self.prefill_chunk
        gov = self.governor
        # Admission order is FIFO without a governor; with one, an
        # over-quota tenant's requests yield to in-quota tenants (but
        # are never skipped outright — see the tenant pick below).
        waiting = list(requests)
        caches = model_mod.init_caches(self.cfg, b, self.max_len,
                                       dtype=self.cache_dtype)
        tokens = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        active: List[Optional[Request]] = [None] * b
        remaining = [0] * b
        req_ctxs: List[Any] = [None] * b
        pf_ctxs: List[Any] = [None] * b
        dec_ctxs: List[Any] = [None] * b
        prefills: Deque[_Prefill] = collections.deque()
        reserved = [False] * b                   # slot held by a prefill
        # Deadlines anchor at submission; keyed by object identity since
        # waiting requests have no engine id yet.
        deadlines = {id(r): time.monotonic() + r.deadline_s
                     for r in requests if r.deadline_s is not None}
        total_tokens = sum(r.max_new_tokens for r in requests)
        agg_id = self._batch_count
        self._batch_count += 1

        def open_ctx(rid, tokens_, phase=None):
            ctx = self._request_ctx(rid, tokens=tokens_, phase=phase)
            ctx.__enter__()
            return ctx

        def close_ctx(ctx):
            if ctx is not None:
                ctx.__exit__(None, None, None)

        def activate(j, r, row, first, next_pos):
            """Request r finished prefill: its row is live in slot j.

            The decode phase span opens before the row insert so the
            prefill/decode spans tile the request span — the insert
            dispatch belongs to serving this request's decode."""
            dec_ctxs[j] = open_ctx(r.id, r.max_new_tokens, phase="decode")
            caches_j = self._insert(caches, row, j)
            tokens[j, 0] = first[0]
            pos[j] = next_pos
            remaining[j] = r.max_new_tokens - 1
            active[j] = r
            r.out.append(int(first[0]))
            if remaining[j] == 0:
                retire(j)
            return caches_j

        def retire(j: int, reason: str = "length") -> None:
            # The caller already fenced this slot's last token (np reads
            # block), so closing the spans here attributes correctly.
            active[j].finish_reason = reason
            close_ctx(dec_ctxs[j])
            dec_ctxs[j] = None
            close_ctx(req_ctxs[j])
            req_ctxs[j] = None
            active[j] = None

        def sweep_deadlines() -> None:
            """Retire every request past its deadline — waiting (drop
            from the queue), mid-prefill (free the reserved slot, close
            the open prefill/request spans), or mid-decode (retire the
            slot, keeping the tokens generated so far)."""
            if not deadlines:
                return
            now = time.monotonic()

            def expired(r: Request) -> bool:
                dl = deadlines.get(id(r))
                return dl is not None and now > dl

            if any(expired(r) for r in waiting):
                kept = []
                for r in waiting:
                    if expired(r):
                        r.finish_reason = "timeout"
                        self._timeouts += 1
                    else:
                        kept.append(r)
                waiting[:] = kept
            for st in [st for st in prefills if expired(st.req)]:
                prefills.remove(st)
                reserved[st.slot] = False
                close_ctx(pf_ctxs[st.slot])
                pf_ctxs[st.slot] = None
                close_ctx(req_ctxs[st.slot])
                req_ctxs[st.slot] = None
                st.req.finish_reason = "timeout"
                self._timeouts += 1
            for j in range(b):
                if active[j] is not None and expired(active[j]):
                    retire(j, reason="timeout")
                    self._timeouts += 1

        def update_gauges():
            self.queue_depth = len(waiting)
            self.live_slots = sum(1 for a in active if a is not None) \
                + sum(reserved)
            self.pending_prefill_chunks = sum(
                max(0, st.toks.shape[1] - st.offset) // chunk
                for st in prefills) if chunk else 0

        with self._measure_ctx(agg_id, tokens=total_tokens):
            try:
                while waiting or prefills \
                        or any(r is not None for r in active):
                    sweep_deadlines()
                    update_gauges()
                    # slot-granular admission: every free slot refills
                    # now (blocking) or enters the chunk queue (chunked)
                    # instead of waiting for the batch to drain.  The
                    # governor gates the rate and picks *which* waiting
                    # request (in-quota tenants first); when it blocks
                    # admission while the engine is completely idle, the
                    # engine admits anyway — power can only be idle draw,
                    # and liveness beats an unholdable cap.
                    for j in range(b):
                        if active[j] is not None or reserved[j] \
                                or not waiting:
                            continue
                        k = 0
                        if gov is not None:
                            if not gov.admission_allowed():
                                if any(a is not None for a in active) \
                                        or prefills:
                                    break
                                gov.note_forced_admit()
                            else:
                                k = next(
                                    (i for i, w in enumerate(waiting)
                                     if gov.tenant_allowed(w.tenant)), 0)
                        r = self._admit(waiting.pop(k))
                        if gov is not None:
                            gov.note_admitted(r)
                        req_ctxs[j] = open_ctx(r.id, r.max_new_tokens)
                        pf_ctxs[j] = open_ctx(r.id, len(r.prompt),
                                              phase="prefill")
                        if chunk:
                            reserved[j] = True
                            prefills.append(
                                self._start_chunked_prefill(r, j))
                            continue
                        # blocking bucketed baseline: whole prompt now
                        t0 = time.perf_counter()
                        first, row, bucket = self._prefill_request(r)
                        if any(a is not None for a in active):
                            self.stall_events.append(
                                time.perf_counter() - t0)
                        close_ctx(pf_ctxs[j])
                        pf_ctxs[j] = None
                        caches = activate(j, r, row, first, bucket)
                    update_gauges()

                    # prefill chunks interleave with each decode step —
                    # one per step by default, 0 while the governor is
                    # shedding load (forced back to 1 when nothing is
                    # decoding: pausing prefill then would idle the
                    # engine, not save power), several when the governor
                    # sees ample headroom.  With no live decode rows the
                    # chunk queue drains back-to-back.
                    if prefills:
                        decode_live = any(a is not None for a in active)
                        budget = 1
                        if gov is not None:
                            budget = gov.prefill_chunk_budget(decode_live)
                            if budget < 1 and not decode_live:
                                budget = 1
                                gov.note_forced_chunk()
                        for _ in range(budget):
                            if not prefills:
                                break
                            st = prefills[0]
                            first = self._step_chunked_prefill(
                                st, decode_live)
                            if first is not None:
                                prefills.popleft()
                                reserved[st.slot] = False
                                close_ctx(pf_ctxs[st.slot])
                                pf_ctxs[st.slot] = None
                                caches = activate(st.slot, st.req,
                                                  st.caches, first,
                                                  st.plen)
                        update_gauges()

                    live = [j for j in range(b) if active[j] is not None]
                    if not live:
                        continue          # everything retired at prefill
                    if gov is not None:
                        # Last-resort lever: duty-cycle decode while
                        # power exceeds the hard-over threshold.
                        gov.maybe_pause_decode()
                    # Retirement is deterministic (exactly max_new_tokens
                    # per request), so with no admission work pending
                    # decode runs device-side until the *next* slot
                    # retires — one host sync per retirement event, not
                    # per token.  While prefill chunks are pending,
                    # decode advances one step per chunk (the
                    # interleave).  Inactive rows decode garbage into
                    # their own (dead, about-to-be-overwritten) cache
                    # rows only.
                    # Under an active power cap decode advances one step
                    # per loop so every step passes the governor's
                    # pause/admission checkpoints.
                    governed = gov is not None and gov.cap_watts is not None
                    steps = 1 if (prefills or governed) \
                        else min(remaining[j] for j in live)
                    if steps > 1 and deadlines \
                            and any(id(active[j]) in deadlines
                                    for j in live):
                        # A deadline'd request must pass the sweep
                        # checkpoint between bursts: bound the
                        # device-side run so it overshoots by at most a
                        # few steps, not the whole request.
                        steps = min(steps, 8)
                    tok_dev = jnp.asarray(tokens)
                    pos_dev = jnp.asarray(pos)
                    outs = []
                    for _ in range(steps):
                        tok_dev, caches = self._decode(
                            self.params, caches, tok_dev, pos_dev,
                            self._next_key())
                        outs.append(tok_dev)
                        pos_dev = pos_dev + 1
                    self.decode_steps += steps
                    self.decode_row_steps += steps * len(live)
                    gen = np.asarray(jnp.concatenate(outs, axis=1))
                    # np read blocked: every token in the chunk is
                    # computed, so spans closed below are correctly
                    # fenced.
                    for j in live:
                        r = active[j]
                        r.out.extend(gen[j].tolist())
                        tokens[j, 0] = gen[j, -1]
                        pos[j] += steps
                        remaining[j] -= steps
                        if remaining[j] == 0:
                            retire(j)
            finally:
                # An exception mid-loop (a prefill OOM — whole-prompt or
                # chunk — or an interrupt) must not leak open
                # request/phase spans: they hold ring-sampler pins on
                # the shared session for its whole lifetime.
                prefills.clear()
                waiting.clear()
                update_gauges()
                for j in range(b):
                    close_ctx(pf_ctxs[j])
                    pf_ctxs[j] = None
                    close_ctx(dec_ctxs[j])
                    dec_ctxs[j] = None
                    close_ctx(req_ctxs[j])
                    req_ctxs[j] = None
        return requests

    # -- paged continuous batching --------------------------------------------
    def _admit_paged(self, r: Request, j: int) -> Optional[_PagedPrefill]:
        """Reserve slot ``j``'s pages for request ``r``: radix-match the
        prompt (mapping cached prefix pages in copy-free), then allocate
        the remaining ``ceil((plen + max_new - 1) / page_size)`` fresh
        pages up front — decode never waits for a page mid-request.
        Returns None when the pool cannot cover it right now (the caller
        leaves the request waiting; retirements free pages).

        Resume (``r.id`` already assigned — the request was preempted or
        drained mid-flight): the effective prompt is
        ``prompt + generated-so-far`` and prefill restarts from the best
        available checkpoint — the radix tree's cached pages (copy-free)
        or the swap store's snapshot (restored by the caller via
        ``swap_restore``), whichever covers more; everything past it is
        honest recompute, accrued to ``wasted_*`` at the learned
        J/token (restored tokens accrue to ``recovered_*``)."""
        pool, radix = self._pool, self._radix
        ps = self.kv_page_size
        resume = r.id is not None
        toks_all = list(r.prompt) + list(r.out)
        plen_eff = len(toks_all)
        pages_needed = math.ceil(
            (len(r.prompt) + r.max_new_tokens - 1) / ps)
        matched: List[int] = []
        if radix is not None:
            _, mpages = radix.match(toks_all)
            # cap the match one token short of the (effective) prompt:
            # the final chunk must re-run >= 1 token for its logits
            use = min(len(mpages), (plen_eff - 1) // ps)
            if use < len(mpages):
                pool.release(mpages[use:])
            matched = mpages[:use]
        # A parked swap snapshot beats a shorter tree match: drop the
        # matched refs and restore the snapshot into fresh pages.
        swap_restore = None
        if resume and r._swap_handle is not None \
                and self.swap_store is not None \
                and r._swap_pages > len(matched):
            n_restore = r._swap_pages
            fresh = pool.alloc(pages_needed)
            if fresh is None and radix is not None:
                radix.evict_for(pages_needed)
                fresh = pool.alloc(pages_needed)
            if fresh is None:
                if matched:
                    pool.release(matched)
                return None         # keep the snapshot for the next try
            if matched:
                pool.release(matched)
            swap_restore = (self.swap_store.get(r._swap_handle), n_restore)
            r._swap_handle = None
            r._swap_pages = 0
            slot_pages, mt = fresh, n_restore * ps
        else:
            fresh = pool.alloc(pages_needed - len(matched))
            if fresh is None and radix is not None:
                radix.evict_for(pages_needed - len(matched))
                fresh = pool.alloc(pages_needed - len(matched))
            if fresh is None:
                if matched:
                    pool.release(matched)
                return None
            slot_pages, mt = matched + fresh, len(matched) * ps
            if resume:
                self._drop_resume_state(r)   # tree won; snapshot is stale
        self._slot_pages[j] = slot_pages
        self._page_table[j, :] = 0
        self._page_table[j, :len(slot_pages)] = slot_pages
        jpt = self._prefill_jpt or 0.0
        if resume:
            # Waste/recovery bookkeeping: of the tokens this request had
            # already computed once (``_progress``), ``mt`` come back
            # from the checkpoint and the rest are re-run.
            rec = min(mt, r._progress)
            waste = max(0, r._progress - mt)
            self.preempt_resumes += 1
            self.recovered_tokens += rec
            self.recovered_joules += rec * jpt
            self.wasted_tokens += waste
            self.wasted_joules += waste * jpt
            self.preempt_log.append({
                "event": "resume", "rid": r.id,
                "rerun_tokens": waste, "recovered_tokens": rec,
                "wasted_joules": waste * jpt,
                "recovered_joules": rec * jpt,
                "via": "swap" if swap_restore is not None else
                       ("tree" if mt else "recompute")})
            self._emit_event(
                "preempt_resume", r.id, rerun_tokens=waste,
                recovered_tokens=rec, wasted_joules=waste * jpt,
                recovered_joules=rec * jpt)
            if self.governor is not None:
                self.governor.note_preempt_resume(r.id, rec, waste)
        else:
            self.prefix_hit_tokens += mt
            if mt and self._prefill_jpt is not None:
                self.saved_prefill_joules += mt * self._prefill_jpt
        toks = np.zeros((plen_eff + self.prefill_chunk,), np.int32)
        toks[:plen_eff] = toks_all
        st = _PagedPrefill(req=r, slot=j, toks=toks, plen=plen_eff,
                           offset=mt, matched_tokens=mt)
        st.swap_restore = swap_restore
        return st

    def _release_slot_pages(self, j: int) -> None:
        if self._slot_pages[j]:
            self._pool.release(self._slot_pages[j])
            self._slot_pages[j] = []
        self._page_table[j, :] = 0

    def _run_paged(self, requests: List[Request]) -> List[Request]:
        """Continuous batching over the paged pools.

        Differences from ``_run_continuous``: admission reserves pages
        instead of a cache row (and may *wait* on the pool, not just on
        slots); prefill chunks write straight into the shared pools
        through the slot's page-table row, with every pending
        admission's chunk batched into ONE (B, chunk) dispatch; decode
        sees a masked page table (mid-prefill / dead rows route to the
        scratch page); retirement adopts the request's full pages into
        the radix prefix tree before releasing its references.
        """
        b = self.batch
        chunk = self.prefill_chunk
        gov = self.governor
        pool, radix = self._pool, self._radix
        ps = self.kv_page_size
        waiting = list(requests)
        caches = self._paged_caches     # pools persist across generate()s
        tokens = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        active: List[Optional[Request]] = [None] * b
        remaining = [0] * b
        req_ctxs: List[Any] = [None] * b
        pf_ctxs: List[Any] = [None] * b
        dec_ctxs: List[Any] = [None] * b
        prefills: Deque[_PagedPrefill] = collections.deque()
        reserved = [False] * b
        deadlines = {id(r): time.monotonic() + r.deadline_s
                     for r in requests if r.deadline_s is not None}
        total_tokens = sum(r.max_new_tokens for r in requests)
        agg_id = self._batch_count
        self._batch_count += 1

        def open_ctx(rid, tokens_, phase=None):
            ctx = self._request_ctx(rid, tokens=tokens_, phase=phase)
            ctx.__enter__()
            return ctx

        def close_ctx(ctx):
            if ctx is not None:
                ctx.__exit__(None, None, None)

        def activate(j, r, first, next_pos):
            # remaining accounts for tokens already generated — a
            # resumed request continues its budget, not a fresh one
            dec_ctxs[j] = open_ctx(r.id, r.max_new_tokens - len(r.out),
                                   phase="decode")
            tokens[j, 0] = int(first)
            pos[j] = next_pos
            remaining[j] = r.max_new_tokens - len(r.out) - 1
            active[j] = r
            r.out.append(int(first))
            if remaining[j] == 0:
                retire(j)

        def retire(j: int, reason: str = "length") -> None:
            r = active[j]
            r.finish_reason = reason
            self._drop_resume_state(r)
            if radix is not None:
                # Adopt the full pages actually written — prompt plus
                # every generated token that was fed back (the last
                # sampled token never lands in the cache) — into the
                # prefix tree BEFORE releasing this request's refs, so
                # adopted pages never transit the free list.  Existing
                # nodes win on duplicate content; timeouts contribute
                # their written prefix like any other retirement.
                written = list(r.prompt) + r.out[:-1]
                n_full = len(written) // ps
                if n_full:
                    radix.insert(written[:n_full * ps],
                                 self._slot_pages[j][:n_full])
            self._release_slot_pages(j)
            close_ctx(dec_ctxs[j])
            dec_ctxs[j] = None
            close_ctx(req_ctxs[j])
            req_ctxs[j] = None
            active[j] = None

        def checkpoint_pages(r, written, pages) -> str:
            """Park a checkpointed request's written full pages for a
            later resume: host swap when configured (frees the device
            pages outright and guarantees a restore), radix tree
            otherwise (copy-free, pool-resident, LRU-evictable — a
            later miss recomputes honestly)."""
            n_full = len(written) // ps
            if not n_full:
                return "none"
            if self.swap_store is not None:
                self._ensure_swap_fns()
                pp = jnp.asarray(self._padded_pages(pages[:n_full]))
                data = [np.asarray(x)
                        for x in self._swap_gather_fn(caches, pp)]
                handle = self.swap_store.put(data, n_full)
                if handle is not None:
                    r._swap_handle = handle
                    r._swap_pages = n_full
                    return "swap"
            if radix is not None:
                radix.insert(list(written)[:n_full * ps], pages[:n_full])
                return "tree"
            return "dropped"

        def requeue(r) -> None:
            """Re-enter the waiting queue with exponential backoff."""
            backoff = self.retry_backoff_s * (2 ** (r._preempts - 1)) \
                if self.retry_backoff_s > 0.0 else 0.0
            r._retry_at = time.monotonic() + backoff
            waiting.append(r)

        def preempt_slot(jj: int) -> None:
            """Preempt the live (decode-phase) request in slot ``jj``:
            checkpoint its written pages, free the slot, requeue it."""
            r = active[jj]
            r._preempts += 1
            if r._preempts > self.preempt_max_retries:
                self.retries_exhausted += 1
                self._emit_event("retry_exhausted", r.id,
                                 preempts=r._preempts - 1)
                retire(jj, reason="preempted-retry-exhausted")
                return
            written = list(r.prompt) + r.out[:-1]
            dest = checkpoint_pages(r, written, self._slot_pages[jj])
            r._progress = len(r.prompt) + len(r.out)
            pages_held = len(self._slot_pages[jj])
            self._release_slot_pages(jj)
            close_ctx(dec_ctxs[jj])
            dec_ctxs[jj] = None
            close_ctx(req_ctxs[jj])
            req_ctxs[jj] = None
            active[jj] = None
            self.preemptions += 1
            requeue(r)
            self.preempt_log.append({
                "event": "preempt", "rid": r.id, "dest": dest,
                "pages_held": pages_held,
                "progress_tokens": r._progress})
            self._emit_event("preempt", r.id, dest=dest,
                             pages=pages_held, progress=r._progress)
            if gov is not None:
                gov.note_preempt(r.id, pages_held, dest)

        def pick_victim(cand) -> Optional[int]:
            """Lowest-priority live decode slot (ties: least progress —
            least recompute waste; then lowest rid for determinism).
            Never preempts *up* the priority order for ``cand``."""
            live_js = [jj for jj in range(b) if active[jj] is not None]
            if not live_js:
                return None
            jj = min(live_js, key=lambda x: (
                active[x].priority,
                len(active[x].prompt) + len(active[x].out),
                active[x].id))
            if cand is not None and active[jj].priority > cand.priority:
                return None
            return jj

        def quarantine(r, jj: int, phase: str) -> None:
            """The step producing ``r``'s next token emitted non-finite
            logits: drop its pages (never adopted — poison must not
            seed the prefix tree), close its spans, and either retry it
            from scratch with backoff or retire it ``"error"``."""
            self.quarantined += 1
            self._emit_event("quarantine", r.id, phase=phase,
                             tokens_kept=len(r.out))
            if gov is not None:
                gov.note_quarantine(r.id)
            self._release_slot_pages(jj)
            close_ctx(dec_ctxs[jj])
            dec_ctxs[jj] = None
            close_ctx(pf_ctxs[jj])
            pf_ctxs[jj] = None
            close_ctx(req_ctxs[jj])
            req_ctxs[jj] = None
            active[jj] = None
            reserved[jj] = False
            self._drop_resume_state(r)
            if self.retry_errors and r._preempts < self.preempt_max_retries:
                r._preempts += 1
                r.out = []          # poisoned run contributes nothing
                r._progress = 0
                requeue(r)
            else:
                r.finish_reason = "error"

        def note_watchdog(dt: float, steps_n: int, phase: str) -> None:
            wd = self.watchdog_step_timeout_s
            if wd is not None and dt / max(1, steps_n) > wd:
                self.hung_steps += 1
                self._emit_event("hung_step", None, phase=phase,
                                 elapsed_s=dt, steps=steps_n)

        def drain_checkpoint() -> None:
            """Graceful drain: checkpoint every in-flight request
            (decode-phase like a preemption but charging no retry,
            mid-prefill parking the chunks already written), leaving
            ``finish_reason`` None so a later ``generate()`` resumes."""
            self.drains += 1
            for jj in range(b):
                if active[jj] is None:
                    continue
                r = active[jj]
                written = list(r.prompt) + r.out[:-1]
                dest = checkpoint_pages(r, written, self._slot_pages[jj])
                r._progress = len(r.prompt) + len(r.out)
                self._release_slot_pages(jj)
                close_ctx(dec_ctxs[jj])
                dec_ctxs[jj] = None
                close_ctx(req_ctxs[jj])
                req_ctxs[jj] = None
                active[jj] = None
                self._emit_event("drain_checkpoint", r.id, dest=dest)
            for st2 in list(prefills):
                r = st2.req
                prefills.remove(st2)
                reserved[st2.slot] = False
                written = list(st2.toks[:st2.offset])
                dest = checkpoint_pages(r, written,
                                        self._slot_pages[st2.slot])
                r._progress = st2.offset
                self._release_slot_pages(st2.slot)
                close_ctx(pf_ctxs[st2.slot])
                pf_ctxs[st2.slot] = None
                close_ctx(req_ctxs[st2.slot])
                req_ctxs[st2.slot] = None
                self._emit_event("drain_checkpoint", r.id, dest=dest)
            self._emit_event("drain", None, unfinished=sum(
                1 for r in requests if r.finish_reason is None))

        def sweep_deadlines() -> None:
            if not deadlines:
                return
            now = time.monotonic()

            def expired(r: Request) -> bool:
                dl = deadlines.get(id(r))
                return dl is not None and now > dl

            if any(expired(r) for r in waiting):
                kept = []
                for r in waiting:
                    if expired(r):
                        # Timeout outranks retry: a preempted request
                        # whose deadline lapses while requeued retires
                        # "timeout", dropping its parked checkpoint.
                        r.finish_reason = "timeout"
                        self._drop_resume_state(r)
                        self._timeouts += 1
                    else:
                        kept.append(r)
                waiting[:] = kept
            for st in [st for st in prefills if expired(st.req)]:
                prefills.remove(st)
                reserved[st.slot] = False
                self._release_slot_pages(st.slot)
                close_ctx(pf_ctxs[st.slot])
                pf_ctxs[st.slot] = None
                close_ctx(req_ctxs[st.slot])
                req_ctxs[st.slot] = None
                st.req.finish_reason = "timeout"
                self._drop_resume_state(st.req)
                self._timeouts += 1
            for j in range(b):
                if active[j] is not None and expired(active[j]):
                    retire(j, reason="timeout")
                    self._timeouts += 1

        def update_gauges():
            self.queue_depth = len(waiting)
            self.live_slots = sum(1 for a in active if a is not None) \
                + sum(reserved)
            self.pending_prefill_chunks = sum(
                math.ceil((st.plen - st.offset) / chunk) for st in prefills)

        # Every part of a loop iteration runs under one phase annotation
        # (see the module docstring's phase vocabulary).
        phase = _Phases()
        with self._measure_ctx(agg_id, tokens=total_tokens):
            try:
                while waiting or prefills \
                        or any(r is not None for r in active):
                    phase("engine/admit")
                    if self._drain_requested:
                        self._drain_requested = False
                        drain_checkpoint()
                        break
                    sweep_deadlines()
                    update_gauges()
                    # Governor-requested shed: preempt (not drop) the
                    # lowest-priority live request; it requeues and
                    # resumes once pressure clears.
                    if self.preempt_enabled and gov is not None \
                            and gov.consume_shed_request():
                        jj = pick_victim(None)
                        if jj is not None:
                            preempt_slot(jj)
                    # Everything waiting may be in retry backoff with
                    # nothing live: sleep off the shortest backoff
                    # instead of spinning on admission.
                    if waiting and not prefills \
                            and all(a is None for a in active):
                        nxt = min(w._retry_at for w in waiting)
                        now_m = time.monotonic()
                        if nxt > now_m:
                            phase("engine/wait")
                            time.sleep(min(nxt - now_m, 0.05))
                            phase("engine/admit")
                    # Admission: governor gate (now fed the pool's free
                    # fraction as a pressure signal) + tenant pick, then
                    # page reservation.  A pool too drained to cover the
                    # next request simply defers it — retirements free
                    # pages; idle-engine exhaustion is impossible because
                    # one slot's worth of pages always fits the pool
                    # (checked in __init__) and an idle pool (after
                    # prefix-tree eviction) is fully free.
                    for j in range(b):
                        if active[j] is not None or reserved[j] \
                                or not waiting:
                            continue
                        now_m = time.monotonic()
                        elig = [i for i, w in enumerate(waiting)
                                if w._retry_at <= now_m]
                        if not elig:
                            break       # all requeued work backing off
                        k = elig[0]
                        if gov is not None:
                            free_frac = pool.free_pages \
                                / max(1, pool.total_pages)
                            if not gov.admission_allowed(
                                    pool_free_frac=free_frac):
                                if any(a is not None for a in active) \
                                        or prefills:
                                    break
                                gov.note_forced_admit()
                            else:
                                k = next(
                                    (i for i in elig
                                     if gov.tenant_allowed(
                                         waiting[i].tenant)), elig[0])
                        cand = waiting[k]
                        st = self._admit_paged(cand, j)
                        if st is None and self.preempt_enabled \
                                and cand.id is None:
                            # Pool exhausted by live requests: preempt
                            # the lowest-priority victim page-aligned
                            # and retry this admission once.  Resumed
                            # candidates never trigger preemption —
                            # evicting live work to readmit previously
                            # evicted work would thrash.
                            jj = pick_victim(cand)
                            if jj is not None:
                                preempt_slot(jj)
                                st = self._admit_paged(cand, j)
                        if st is None:
                            # Pool short (even after radix eviction):
                            # leave the request waiting for retirements
                            # to free pages — but say so, once per
                            # episode, instead of silently spinning
                            # through this checkpoint.
                            if not self._pool_short:
                                self._pool_short = True
                                self.pool_wait_events += 1
                                if gov is not None:
                                    need = math.ceil(
                                        (len(cand.prompt)
                                         + cand.max_new_tokens - 1)
                                        / ps)
                                    gov.note_pool_wait(pool.free_pages,
                                                       need)
                            break
                        if self._pool_short:
                            self._pool_short = False
                            if gov is not None:
                                gov.note_pool_ready()
                        # Pages are committed to slot j from here on:
                        # mark it reserved BEFORE anything below can
                        # raise, so the finally block releases them.
                        reserved[j] = True
                        waiting.pop(k)
                        r = cand
                        if r.id is None:
                            r = self._admit(r)
                            if gov is not None:
                                gov.note_admitted(r)
                        if st.swap_restore is not None:
                            # Scatter the parked snapshot into the fresh
                            # pages; prefill then continues at its edge.
                            data, npg = st.swap_restore
                            self._ensure_swap_fns()
                            pp = jnp.asarray(self._padded_pages(
                                self._slot_pages[j][:npg]))
                            caches = self._swap_scatter_fn(
                                caches, [jnp.asarray(d) for d in data],
                                pp)
                            st.swap_restore = None
                        req_ctxs[j] = open_ctx(
                            r.id, r.max_new_tokens - len(r.out))
                        # phase span counts the tokens actually
                        # prefilled — a prefix hit shrinks the work
                        pf_ctxs[j] = open_ctx(
                            r.id, st.plen - st.matched_tokens,
                            phase="prefill")
                        prefills.append(st)
                    update_gauges()

                    if prefills:
                        phase("engine/prefill")
                        decode_live = any(a is not None for a in active)
                        budget = 1
                        if gov is not None:
                            budget = gov.prefill_chunk_budget(decode_live)
                            if budget < 1 and not decode_live:
                                budget = 1
                                gov.note_forced_chunk()
                        for _ in range(budget):
                            if not prefills:
                                break
                            # Batched chunk admissions: ONE (B, chunk)
                            # dispatch advances EVERY pending prefill by
                            # one chunk — each row at its own offset,
                            # passenger rows masked with last_idx=-1.
                            t0 = time.perf_counter()
                            ctoks = np.zeros((b, chunk), np.int32)
                            offs = np.zeros((b,), np.int32)
                            last = np.full((b,), -1, np.int32)
                            for st in prefills:
                                ctoks[st.slot] = \
                                    st.toks[st.offset:st.offset + chunk]
                                offs[st.slot] = st.offset
                                last[st.slot] = min(
                                    st.plen - 1 - st.offset, chunk - 1)
                            tok, okp, caches = \
                                self._paged_prefill_chunk_fn(
                                    self.params, caches,
                                    jnp.asarray(ctoks),
                                    jnp.asarray(offs), jnp.asarray(last),
                                    jnp.asarray(self._page_table),
                                    self._next_key())
                            with jax.profiler.TraceAnnotation(
                                    "engine/prefill/fetch"):
                                tok = np.asarray(tok)   # fence the dispatch
                                okp = np.asarray(okp)
                            dt = time.perf_counter() - t0
                            note_watchdog(dt, 1, "prefill")
                            if decode_live:
                                self.stall_events.append(dt)
                            for st in list(prefills):
                                st.offset += chunk
                                if st.offset >= st.plen:
                                    prefills.remove(st)
                                    reserved[st.slot] = False
                                    if not okp[st.slot]:
                                        # sampled-from logits were
                                        # non-finite: poisoned request
                                        quarantine(st.req, st.slot,
                                                   "prefill")
                                        continue
                                    close_ctx(pf_ctxs[st.slot])
                                    pf_ctxs[st.slot] = None
                                    activate(st.slot, st.req,
                                             tok[st.slot], st.plen)
                        update_gauges()

                    phase("engine/decode")
                    live = [j for j in range(b) if active[j] is not None]
                    if not live:
                        continue
                    if gov is not None:
                        gov.maybe_pause_decode()
                    governed = gov is not None and gov.cap_watts is not None
                    steps = 1 if (prefills or governed) \
                        else min(remaining[j] for j in live)
                    if steps > 1 and deadlines \
                            and any(id(active[j]) in deadlines
                                    for j in live):
                        steps = min(steps, 8)
                    # Decode sees a MASKED page table: only actively
                    # decoding rows expose their pages — mid-prefill and
                    # dead rows read/write the scratch page only, so
                    # their garbage decode tokens cannot touch pages a
                    # prefill is filling.
                    mask = np.zeros((b, 1), np.int32)
                    for j in live:
                        mask[j] = 1
                    pt_dec = jnp.asarray(self._page_table * mask)
                    tok_dev = jnp.asarray(tokens)
                    pos_dev = jnp.asarray(pos)
                    outs = []
                    oks = []
                    t0d = time.perf_counter()
                    for _ in range(steps):
                        tok_dev, ok_dev, caches = self._paged_decode(
                            self.params, caches, tok_dev, pos_dev, pt_dec,
                            self._next_key())
                        outs.append(tok_dev)
                        oks.append(ok_dev)
                        pos_dev = pos_dev + 1
                    self.decode_steps += steps
                    self.decode_row_steps += steps * len(live)
                    with jax.profiler.TraceAnnotation("engine/decode/fetch"):
                        gen = np.asarray(jnp.concatenate(outs, axis=1))
                        okm = np.asarray(jnp.stack(oks, axis=1))
                    note_watchdog(time.perf_counter() - t0d, steps,
                                  "decode")
                    phase("engine/retire")
                    for j in live:
                        r = active[j]
                        row_ok = okm[j]
                        if not row_ok.all():
                            # Keep the tokens sampled from finite logits
                            # (strictly before the first bad step), then
                            # quarantine the request — its pages are
                            # poisoned past that point.
                            good = int(np.argmax(~row_ok))
                            r.out.extend(gen[j, :good].tolist())
                            quarantine(r, j, "decode")
                            continue
                        r.out.extend(gen[j].tolist())
                        tokens[j, 0] = gen[j, -1]
                        pos[j] += steps
                        remaining[j] -= steps
                        if remaining[j] == 0:
                            retire(j)
            finally:
                # Exceptions must leak neither spans nor page refs; the
                # (possibly donated) cache tree is re-bound so the next
                # generate() resumes from live buffers.
                self._paged_caches = caches
                self._pool_short = False
                for j in range(b):
                    if active[j] is not None or reserved[j]:
                        self._release_slot_pages(j)
                    active[j] = None
                    reserved[j] = False
                prefills.clear()
                waiting.clear()
                update_gauges()
                for j in range(b):
                    close_ctx(pf_ctxs[j])
                    pf_ctxs[j] = None
                    close_ctx(dec_ctxs[j])
                    dec_ctxs[j] = None
                    close_ctx(req_ctxs[j])
                    req_ctxs[j] = None
                phase.end()
        return requests

    # -- synchronized waves (baseline) ---------------------------------------
    def _run_wave(self, wave: List[Request]) -> List[Request]:
        b = self.batch
        plen = prompt_bucket(max(len(r.prompt) for r in wave),
                             self.min_prompt_bucket)
        toks = np.zeros((b, plen), np.int32)
        for j, r in enumerate(wave):
            toks[j, plen - len(r.prompt):] = r.prompt   # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.is_encoder_decoder:
            batch["frame_embeds"] = jnp.zeros(
                (b, self.cfg.enc_len, self.cfg.d_model), jnp.bfloat16)

        steps = max(r.max_new_tokens for r in wave)
        # Wave-level capacity check: rows share the wave-max prompt
        # bucket AND decode wave-max steps, so a long-prompt neighbour
        # can push a short request's positions past max_len even though
        # each request passed its own check — dynamic_update_slice would
        # then clamp-corrupt the last cache slot silently.
        if plen + steps > self.max_len + 1:
            raise ValueError(
                f"wave needs {plen + steps} cache slots (shared prompt "
                f"bucket {plen} + {steps} decode steps) but max_len is "
                f"{self.max_len}; shrink the wave or use continuous mode")
        # J/token must divide by tokens actually generated — padded rows
        # and early-retired slots burn decode FLOPs but emit nothing.
        gen_tokens = sum(r.max_new_tokens for r in wave)
        wave_id = self._batch_count
        self._batch_count += 1
        with self._measure_ctx(wave_id, tokens=gen_tokens):
            nxt, caches = self._prefill(self.params, batch,
                                        self._next_key())
            nxt = nxt[:, None]
            cur = plen
            outs = [nxt]
            for _ in range(steps - 1):
                nxt, caches = self._decode(self.params, caches, nxt,
                                           jnp.asarray(cur, jnp.int32),
                                           self._next_key())
                outs.append(nxt)
                cur += 1
            gen = jax.block_until_ready(jnp.concatenate(outs, axis=1))
        gen = np.asarray(gen)
        for j, r in enumerate(wave):
            r.out = gen[j, :r.max_new_tokens].tolist()
            r.finish_reason = "length"
        return wave
