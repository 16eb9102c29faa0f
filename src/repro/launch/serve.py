"""Serving launcher: continuous-batching ServeEngine with PMT J/token
accounting — aggregate and per-request — plus the energy control plane:
live HTTP/SSE telemetry and power-capped scheduling.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --reduced --requests 8 --max-new 16 [--mode wave]

  # hold the run under 120 W and watch it live:
  PYTHONPATH=src python -m repro.launch.serve --reduced \
      --power-cap-watts 120 --telemetry-port 8321
  curl -N http://127.0.0.1:8321/stream        # live SSE record feed
  curl http://127.0.0.1:8321/timeline         # power series
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

import repro.core as pmt
from repro import configs
from repro.core.backends.dummy import DummySensor
from repro.core.supervisor import SensorSupervisor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_mod
from repro.serve.engine import Request, ServeEngine, stall_p95
from repro.serve.governor import PowerGovernor
from repro.telemetry import PowerRecorder, TelemetryServer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=list(configs.ARCH_NAMES))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "wave"])
    ap.add_argument("--decode-attn-impl", default="auto",
                    choices=["auto", "dense", "flash"],
                    help="decode attention path: flash = length-aware "
                         "kernels/decode_attention (Pallas on TPU, "
                         "masked-lax sweep elsewhere); auto = flash on "
                         "TPU only")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill chunk size (tokens per "
                         "admission slice interleaved with decode); 0 = "
                         "blocking bucketed prefill baseline; default "
                         "resolves PMT_PREFILL_CHUNK then "
                         "cfg.prefill_chunk")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="KV cache layout: paged = block page pools + "
                         "per-request page tables + radix prefix reuse "
                         "(continuous mode only); contiguous = the "
                         "per-slot baseline")
    ap.add_argument("--cache-dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8", "fp8_e4m3"],
                    help="KV cache storage: bfloat16/float32 store raw "
                         "values; int8/fp8_e4m3 store quantized codes + "
                         "per-row scales, dequantized in-register by the "
                         "attention kernels (~2x smaller cache, bounded "
                         "logit drift — see BENCH_quant.json)")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="tokens per KV page (paged layout); default "
                         "cfg.kv_page_size")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="total pages in the shared pool (paged layout); "
                         "default batch * ceil(max_len / page_size). "
                         "Smaller pools trade admission waits for cache "
                         "memory; prefix-tree pages are evicted LRU "
                         "under pressure")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="radix-tree prefix reuse across requests "
                         "(paged layout; default on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--preempt", action="store_true",
                    help="preemption-and-resume: on pool exhaustion (or "
                         "governor shedding) the lowest-priority live "
                         "request is preempted page-aligned — its written "
                         "pages checkpoint to the radix tree (or host "
                         "swap, see --swap-bytes) and it resumes via "
                         "chunked prefill once pressure clears (paged "
                         "layout only)")
    ap.add_argument("--preempt-max-retries", type=int, default=8,
                    help="preemptions a request survives before retiring "
                         "with reason 'preempted-retry-exhausted'")
    ap.add_argument("--retry-backoff-s", type=float, default=0.0,
                    help="base of the exponential re-admission backoff "
                         "for preempted/quarantined requests (0 = "
                         "immediate retry)")
    ap.add_argument("--swap-bytes", type=int, default=None,
                    help="host-memory swap budget for preempted pages "
                         "(bytes); unset = radix-tree checkpoints only")
    ap.add_argument("--retry-errors", action="store_true",
                    help="restart NaN/Inf-quarantined requests from "
                         "scratch (bounded by --preempt-max-retries) "
                         "instead of retiring them with reason 'error'")
    ap.add_argument("--watchdog-step-timeout-s", type=float, default=None,
                    help="engine watchdog: per-step wall-clock budget; a "
                         "fenced device step exceeding it counts a "
                         "hung_step and emits a telemetry event")
    ap.add_argument("--pool-reserve-frac", type=float, default=0.0,
                    help="governor admission veto when the page pool's "
                         "free fraction drops below this reserve "
                         "(paged layout + governor only; 0 disables)")
    ap.add_argument("--power-cap-watts", type=float, default=None,
                    help="hold measured window power under this budget "
                         "via the PowerGovernor (admission gating, "
                         "prefill-chunk pacing, decode duty-cycling); "
                         "continuous mode only")
    ap.add_argument("--tenant-quota", type=float, default=None,
                    help="per-tenant joules quota: requests round-robin "
                         "over synthetic tenants, and an over-quota "
                         "tenant yields admission priority to in-quota "
                         "ones (soft — never starved)")
    ap.add_argument("--request-deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline (from "
                         "submission): requests still waiting or "
                         "mid-generation past it finish with reason "
                         "'timeout', keeping partial output; continuous "
                         "mode only")
    ap.add_argument("--signal-ttl-s", type=float, default=None,
                    help="governor power-signal freshness budget: when "
                         "the newest watts sample is older than this the "
                         "signal is stale and the governor degrades per "
                         "--governor-fail-mode")
    ap.add_argument("--governor-fail-mode", default="closed",
                    choices=["closed", "open"],
                    help="stale-signal policy: closed = stop admitting / "
                         "zero the prefill budget until the signal "
                         "recovers (protects the power budget); open = "
                         "run unthrottled (protects availability)")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap each backend in a SensorSupervisor with a "
                         "fail-safe dummy fallback: reads get deadline/"
                         "retry/circuit-breaker protection and fail over "
                         "instead of killing the sampler thread")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    help="serve live telemetry on this HTTP port "
                         "(/timeline /requests /stats /stream SSE); "
                         "0 = ephemeral (port printed at startup)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for decode; 0 (default) "
                         "= greedy argmax")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_config(args.arch, reduced=args.reduced)
    params, _ = model_mod.init_params(jax.random.PRNGKey(args.seed), cfg)
    # One shared session: the aggregate batch region and every request's
    # flat serve/req<N> span are O(1) enqueues; energy resolves on the
    # background resolver thread into the MemoryExporter — the serving
    # thread never waits.
    backends = ["cpuutil", "tpu"]
    if args.supervise:
        # Fail-safe chain per backend: the real sensor first, a 0 W dummy
        # last so a dead backend degrades measurements instead of the run.
        backends = [SensorSupervisor([pmt.create(name),
                                      DummySensor(watts=0.0)],
                                     deadline_s=0.25)
                    for name in backends]
    session = pmt.Session(backends)
    energy = session.add_exporter(pmt.MemoryExporter())

    # Control plane: recorder aggregates records + watts timelines; the
    # governor (if capped) reads its smoothed window from it; the HTTP
    # server (if requested) serves both live.
    recorder = PowerRecorder().attach(session, exporter=energy)
    governor = None
    if (args.power_cap_watts is not None or args.tenant_quota is not None) \
            and args.mode == "continuous":
        governor = PowerGovernor(recorder,
                                 cap_watts=args.power_cap_watts,
                                 tenant_quota_j=args.tenant_quota,
                                 signal_ttl_s=args.signal_ttl_s,
                                 fail_mode=args.governor_fail_mode,
                                 pool_reserve_frac=args.pool_reserve_frac)
    server = None
    if args.telemetry_port is not None:
        server = TelemetryServer(recorder, port=args.telemetry_port).start()
        print(f"telemetry: {server.url} "
              f"(/timeline /requests /stats /stream)")

    swap_store = None
    if args.swap_bytes is not None:
        from repro.serve.swap import PageSwapStore
        swap_store = PageSwapStore(capacity_bytes=args.swap_bytes)
    engine = ServeEngine(cfg, params, batch_size=args.batch,
                         max_len=args.max_len, session=session,
                         mode=args.mode,
                         decode_attn_impl=args.decode_attn_impl,
                         prefill_chunk=args.prefill_chunk,
                         governor=governor,
                         kv_layout=args.kv_layout,
                         kv_page_size=args.kv_page_size,
                         kv_pool_pages=args.kv_pool_blocks,
                         prefix_cache=args.prefix_cache,
                         cache_dtype=args.cache_dtype,
                         preempt=args.preempt,
                         preempt_max_retries=args.preempt_max_retries,
                         retry_backoff_s=args.retry_backoff_s,
                         retry_errors=args.retry_errors,
                         swap_store=swap_store,
                         watchdog_step_timeout_s=(
                             args.watchdog_step_timeout_s),
                         greedy=args.temperature <= 0.0,
                         temperature=args.temperature or 1.0,
                         seed=args.seed)
    recorder.attach_engine(engine)

    rng = np.random.default_rng(args.seed)
    # heterogeneous lengths: the workload continuous batching is for
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=rng.integers(2, 9)).tolist(),
                    max_new_tokens=int(rng.integers(2, args.max_new + 1)),
                    tenant=(f"tenant{i % 2}" if args.tenant_quota is not None
                            else None),
                    deadline_s=(args.request_deadline_s
                                if args.mode == "continuous" else None))
            for i in range(args.requests)]
    done = engine.generate(reqs)
    n_tokens = sum(len(r.out) for r in done)
    for i, r in enumerate(done[:4]):
        print(f"req{i}: prompt={r.prompt} -> {r.out}")
    session.flush()              # settle any spans still in flight
    recorder.poll_once()         # final watts tail into the timeline
    per_req = [r for r in energy.records if r.path.startswith("serve/req")]
    agg = [r for r in energy.records
           if not r.path.startswith(("serve/req", "serve/governor"))]
    agg_j = sum(r.joules for r in agg)
    print(f"served {len(done)} requests, {n_tokens} tokens "
          f"[{args.mode}], {agg_j:.2f} J aggregate, "
          f"{agg_j / max(n_tokens, 1):.4f} J/token "
          f"(stats: {session.stats()})")
    if per_req:
        by_req = {}
        for r in per_req:
            path, _, phase = r.path.partition("serve/")[2].partition("/")
            d = by_req.setdefault(f"serve/{path}",
                                  {"joules": 0.0, "tokens": 0,
                                   "prefill": 0.0, "decode": 0.0})
            if phase:
                d[phase] += r.joules
            else:
                d["joules"] += r.joules
                d["tokens"] = r.tokens
        worst = max(by_req.items(),
                    key=lambda kv: kv[1]["joules"] / max(kv[1]["tokens"], 1))
        print(f"per-request spans: {len(by_req)} "
              f"(token sum {sum(d['tokens'] for d in by_req.values())}); "
              f"costliest {worst[0]}: "
              f"{worst[1]['joules'] / max(worst[1]['tokens'], 1):.4f} J/token "
              f"({worst[1]['prefill']:.2f} J prefill / "
              f"{worst[1]['decode']:.2f} J decode)")

    # end-of-run scheduler report: stalls, retraces, throttle decisions
    st = engine.stats()
    report = (f"scheduler: {st['stall_events']} decode stalls "
              f"(p95 {st['stall_p95_s'] * 1e3:.2f} ms"
              f"{', each bounded by one chunk' if engine.prefill_chunk else ''}"
              f"), compiles {st['compile_counts']}")
    if args.request_deadline_s is not None:
        report += f", {st['requests_timed_out']} timed out"
    if governor is not None:
        g = st["governor"]
        watts = recorder.mean_watts(governor.window_s)
        report += (f"; governor: {g['throttle_decisions']} throttle "
                   f"decisions {g['throttle_actions']}, "
                   f"{g['pause_total_s'] * 1e3:.1f} ms paused, "
                   f"window {watts if watts is None else round(watts, 1)} W "
                   f"vs cap {g['cap_watts']} W")
        if g["tenant_joules"]:
            report += f", tenant J {g['tenant_joules']}"
    print(report)
    kc = st["kv_cache"]
    print(f"kv cache: {kc['cache_dtype']}, "
          f"{kc['bytes_per_token']:.1f} B/token")
    if args.kv_layout == "paged":
        line = (f"kv pool: {kc['pages_used']}/{kc['pages_total']} pages "
                f"held ({kc['pages_free']} free, {kc['page_size']} "
                f"tokens/page, {kc['pool_wait_events']} pool waits)")
        if kc["prefix_cache"]:
            line += (f"; prefix cache: {kc['prefix_hits']}/"
                     f"{kc['prefix_lookups']} hits, "
                     f"{kc['prefix_hit_tokens']} prompt tokens reused, "
                     f"{kc['prefix_evictions']} evictions, "
                     f"~{kc['saved_prefill_joules']:.2f} J prefill saved")
        print(line)
        pre = st["preemption"]
        if pre["enabled"] or pre["quarantined"] or pre["hung_steps"]:
            pline = (f"preemption: {pre['preemptions']} preempted, "
                     f"{pre['resumes']} resumed "
                     f"({pre['recovered_tokens']} tokens recovered "
                     f"~{pre['recovered_joules']:.2f} J, "
                     f"{pre['wasted_tokens']} re-run "
                     f"~{pre['wasted_joules']:.2f} J wasted), "
                     f"{pre['retries_exhausted']} retries exhausted, "
                     f"{pre['quarantined']} quarantined, "
                     f"{pre['hung_steps']} hung steps")
            if "swap" in pre:
                sw = pre["swap"]
                pline += (f"; swap: {sw['swapped_out_pages']} pages out / "
                          f"{sw['swapped_in_pages']} in, "
                          f"{sw['bytes_used']} B held, "
                          f"{sw['rejected_puts']} rejected")
            print(pline)
    if args.supervise:
        health = recorder.health()
        print(f"measurement plane: {health['state']} "
              f"({health['health_events']} health transitions)")

    if server is not None:
        server.close()
    if governor is not None:
        governor.close()
    recorder.close()
    session.close()


if __name__ == "__main__":
    main()
