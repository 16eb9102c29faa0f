"""One run of one benchmark cell.

A cell (``BENCHMARK.json``'s ``workloads``) is a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<mix>.json``).
A run:

1. makes the weights on the device from the seed, in bfloat16, in one
   jitted call (``reference/<reference>.py`` draws them; the config's
   family, ``families/<reference>.py``, puts them into the program's
   tree);
2. builds the engine as operators run it, with a ``pmt.Session`` on the
   modelled ``tpu`` backend, and warms up every program the window uses:
   one prefill chunk and one decode step at the cell's batch, and each
   decode burst length from 1 to 8;
3. hands every request of the mix to one ``generate()`` call, each
   held back by its admission gate until it is due.  Open-loop traffic
   starts ``warm_s`` seconds (the mix's) before the window opens, so
   that the window sees the engine at its steady load; that lead-in is
   set-up.  The run is cut with ``drain()`` (see ``cut``): a backlog at
   the close, open-loop traffic once every request due has its first
   token;
4. reads the metrics from the window's requests (their spans and the
   times their tokens reached the host), the engine's counters and,
   with ``--trace 1``, a profiler trace of the run;
5. frees the program, runs the float32 reference over a sample of the
   finished requests and compares what was served (``check.py``).

The last line of standard output is one JSON object; the numbers
compared are the last lines of standard error.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
CACHE_DIR = CHECKOUT / ".jax_cache"
GRACE_S = 60.0          # how long past the close a request may finish
WARM_BURSTS = 8         # decode bursts are at most 8 steps (see warm_up)

for p in (str(HERE), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Served:
    """One request of the window, as the spans and the engine saw it
    (times in seconds on the monotonic clock)."""

    rid: Optional[int]
    due: float
    prompt_len: int
    served: int
    admit: Optional[float] = None
    first: Optional[float] = None   # end of the prefill span (fenced)
    cached: int = 0                 # prompt tokens from the prefix cache
    times: List = dataclasses.field(default_factory=list)
    # ^ (tokens so far, time) as each burst of tokens reached the host


@dataclasses.dataclass
class Run:
    """What a reader of a per-layer metric may read."""

    config: Dict
    family: Any                     # families/<reference>.py: work counts
    chunk: int
    t0: float
    window_s: float
    requests: List[Served]          # due in the window
    lead_in: List[Served]           # due before it (open loop, warm_s)
    stats0: Dict
    stats1: Dict
    stall_events: List[float]
    peaks: Dict
    trace: Any = None               # trace.Trace, kinds assigned
    trace_window_s: float = 0.0

    @property
    def all_requests(self) -> List[Served]:
        """The window's requests and those before it: all the work a
        trace of the run holds."""
        return self.lead_in + self.requests


# -- specification ---------------------------------------------------------

def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: pathlib.Path, name: str):
    """(benchmark, cell, config, mix) for cell ``name`` under ``root``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / cfg_entry["file"])
    mix = load_json(root / bench["paths"][0] / "traffic"
                    / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def metrics_for(bench: Dict, cell: str, group: str) -> List[Dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def peaks_for(kind: str) -> Dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table['chips'])})")
    return table["chips"][kind]


def load_module(path: pathlib.Path):
    """The module in file ``path``, by file, so that a benchmark root
    other than this directory (the tests') brings its own."""
    name = "perfbench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(bench_dir: pathlib.Path, name: str) -> Callable:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read


def reference(bench_dir: pathlib.Path, config: Dict):
    """The plain reference ``reference/<reference>.py`` of a config."""
    return load_module(bench_dir / "reference" / f"{config['reference']}.py")


def family(bench_dir: pathlib.Path, config: Dict):
    """The family ``families/<reference>.py`` of a config: the sizes its
    program config states, the program's tree, the work it counts."""
    return load_module(bench_dir / "families" / f"{config['reference']}.py")


# -- statistics ------------------------------------------------------------

def percentile(xs, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least
    ``q`` percent of the sample at or below it."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


# -- set-up ----------------------------------------------------------------

def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    if require_accelerator and (devs[0].platform == "cpu"
                                or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs


class CompileCounter:
    """Counts tracing, compiling and compile-cache loads while on.  One
    per process: JAX keeps its listeners for good."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    _one: Optional["CompileCounter"] = None

    def __init__(self):
        from jax import monitoring
        self.on = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.count = 0
        return cls._one

    def _dur(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1

    def _event(self, name, **_kw):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.count += 1


def warm_up(engine, Request, deadline_s: float) -> None:
    """Compile everything the window runs.  Requests carry a deadline,
    so the engine decodes at most 8 steps between scheduler checks;
    one request alone with ``k + 1`` tokens to serve makes one burst of
    ``k`` steps, whose outputs the engine joins in a program of its own.
    One-token prompts fill no page, so the prefix cache keeps nothing."""
    for k in range(1, WARM_BURSTS + 1):
        engine.generate([Request(prompt=[1], max_new_tokens=k + 1,
                                 deadline_s=deadline_s)])


# -- the window ------------------------------------------------------------

def cut(engine, reqs, close: float, grace: float, done) -> None:
    """End the run with ``engine.drain()``: at the close for a backlog;
    for open-loop traffic once every request due in the window has its
    first token, at most ``grace`` seconds after the close.  Requests
    still decoding then keep the tokens they were served."""
    if done.wait(max(0.0, close - time.monotonic())):
        return
    while time.monotonic() < close + grace and not all(
            r.out or r.finish_reason for r in reqs):
        if done.wait(0.02):
            return
    engine.drain()


def spans_by_rid(records) -> Dict[int, Dict[str, Any]]:
    out: Dict[int, Dict[str, Any]] = {}
    for r in records:
        if not r.path.startswith("serve/req"):
            continue
        head, _, phase = r.path[len("serve/req"):].partition("/")
        out.setdefault(int(head), {})[phase or "req"] = r
    return out


def collect(specs, reqs, records, t0: float) -> List[Served]:
    spans = spans_by_rid(records)
    out = []
    for s, r in zip(specs, reqs):
        sv = Served(rid=r.id, due=t0 + s.due_s, prompt_len=len(s.prompt),
                    served=len(r.out), times=list(r.out.times))
        sp = spans.get(r.id, {}) if r.id is not None else {}
        if "req" in sp:
            sv.admit = sp["req"].start_s
        if "prefill" in sp:
            pf = sp["prefill"]
            if pf.tokens is not None:
                sv.cached = sv.prompt_len - int(pf.tokens)
            if sv.served:
                sv.first = pf.end_s
        out.append(sv)
    return out


def end_to_end(name: str, run: Run) -> Optional[float]:
    reqs = run.requests
    if name == "ttft_p90_ms":
        never = run.t0 + run.window_s + GRACE_S
        ttft = [((r.first if r.first is not None else never) - r.due) * 1e3
                for r in reqs]
        return percentile(ttft, 90)
    if name == "tpot_p90_ms":
        # From the first token to the last that reached the host, over
        # the tokens between; a request cut mid-decode counts what it
        # was served, not the cut.
        tpot = [(r.times[-1][1] - r.times[0][1])
                / (r.times[-1][0] - r.times[0][0]) * 1e3
                for r in reqs if r.times and r.times[-1][0] > r.times[0][0]]
        return percentile(tpot, 90)
    if name == "out_tok_per_s":
        return sum(r.served for r in reqs) / run.window_s
    raise KeyError(f"no end-to-end metric {name!r}")


# -- one run ---------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: pathlib.Path = CHECKOUT,
             require_accelerator: bool = True,
             hook: Optional[Callable] = None,
             compile_cache: bool = True,
             mix_update: Optional[Dict] = None,
             control: bool = False,
             details: Optional[Dict] = None,
             check_outputs: bool = True) -> Dict:
    """Run cell ``name`` once and return the result line's object.

    For the tools beside the benchmark (``limits.py``, ``sweep.py``)
    and the tests: ``hook(engine)`` may replace parts of the engine
    before the window (the tests break the timed path with it);
    ``mix_update`` changes the mix's parameters (a rate sweep);
    ``control`` judges the control in the program's place: the tokens
    the fp8 reference ranks first, on the same prompts and served
    tokens, must fail the limit; ``details``, a dict, receives the
    ``Run``, the comparison's verdict and ``compare(config, fp8)``,
    which compares the same tokens against the reference of another
    config; ``check_outputs=False`` skips the comparison (a sweep) and
    returns the metrics alone."""
    import jax

    import check
    import program
    import traffic
    import xplane

    bench, cell, config, mix = cell_spec(root, name)
    mix = {**mix, **(mix_update or {})}
    bench_dir = root / bench["paths"][0]
    devs = check_devices(cell["chips"], require_accelerator)
    if compile_cache:
        enable_compile_cache()
    counter = CompileCounter.get()
    fam = family(bench_dir, config)
    mcfg = program.model_config(config, fam)
    eng_cfg = {**config["serve"], **mix.get("engine", {})}
    ref = reference(bench_dir, config)
    backlog = mix["kind"] == "backlog"

    key = ref.key_from_seed(seed)
    t_weights = time.monotonic()
    params = jax.jit(lambda k: fam.layout(config, ref.make_weights(
        config, k)))(key)
    jax.block_until_ready(params)
    program.check_layout(mcfg, params)
    t_engine = time.monotonic()
    session, exporter = program.make_session()
    engine = program.make_engine(mcfg, params, eng_cfg, session)
    if hook is not None:
        hook(engine)
    deadline = seconds + GRACE_S + 30.0     # the cut comes first
    t_warm = time.monotonic()
    warm_up(engine, program.Request, deadline)
    t_ready = time.monotonic()
    compiled = dict(engine.compile_counts)
    chunk = engine.prefill_chunk
    specs = traffic.generate(mix, seed, seconds, config["vocab_size"],
                             chunk, eng_cfg["max_len"])
    reqs = [program.TimedRequest(prompt=s.prompt.tolist(),
                                 max_new_tokens=s.max_new,
                                 deadline_s=deadline) for s in specs]
    stats0 = engine.stats()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    t_trace = time.monotonic()

    lead = 0.0 if backlog else float(mix.get("warm_s", 0.0))
    t_first = time.monotonic() + 0.01
    t0 = t_first + lead                     # the window opens
    for s, r in zip(specs, reqs):
        r._retry_at = t0 + s.due_s
    done_event = threading.Event()
    cutter = threading.Thread(target=cut, daemon=True, args=(
        engine, reqs, t0 + seconds, 0.0 if backlog else GRACE_S,
        done_event))
    cutter.start()
    counter.on = True
    setup_s = t0 - t_start
    late_s = max(0.0, time.monotonic() - t_first)
    engine.generate(reqs)
    t_end = time.monotonic()
    counter.on = False
    done_event.set()
    cutter.join()
    t_trace_end = time.monotonic()
    if trace:
        jax.profiler.stop_trace()

    stats1 = engine.stats()
    stall = list(engine.stall_events)
    session.flush()
    records = list(exporter.records)
    served = collect(specs, reqs, records, t0)
    in_window = [sv for s, sv in zip(specs, served) if s.due_s >= 0]
    used = devs[:cell["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    window_s = (t_end - t0) if backlog else seconds
    run = Run(config=config, family=fam, chunk=chunk, t0=t0,
              window_s=window_s, requests=in_window,
              lead_in=[sv for s, sv in zip(specs, served) if s.due_s < 0],
              stats0=stats0,
              stats1=stats1, stall_events=stall,
              peaks=peaks_for(devs[0].device_kind)
              if devs[0].platform != "cpu" else {})
    if details is not None:
        details["run"] = run
        details["ended_s"] = t_end - t0
    notes = [f"set-up {setup_s:.3f} s: to JAX and the devices "
             f"{t_weights - t_start:.3f}, weights {t_engine - t_weights:.3f}"
             f", engine and pool {t_warm - t_engine:.3f}, warm-up "
             f"{t_ready - t_warm:.3f}, traffic {t_first - t_ready:.3f}, "
             f"lead-in {lead:.3f}",
             f"generator late by {late_s * 1e3:.3f} ms at the first due",
             f"compiles in the window: {counter.count}; step programs "
             f"compiled {compiled} -> {dict(engine.compile_counts)}",
             f"requests {len(in_window)} in the window and "
             f"{len(reqs) - len(in_window)} before it, window "
             f"{window_s:.3f} s, run ended "
             f"{t_end - t0:.3f} s after the opening"]

    result: Dict[str, Any] = {}
    if trace:
        tr = xplane.load(xplane.find_xplane(log_dir),
                         list(program.KERNELS.values()))
        xplane.assign_ops(tr, {
            program.KERNELS["decode_attention"]: "decode",
            program.KERNELS["prefill_attention"]: "prefill_chunk"})
        shutil.rmtree(log_dir, ignore_errors=True)
        run.trace = tr
        run.trace_window_s = t_trace_end - t_trace
        metrics = {}
        for m in metrics_for(bench, name, "per_layer"):
            v = reader(bench_dir, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in xplane.top_ops(tr)],
            "idle_gaps": [[k, v] for k, v in xplane.idle_gaps(tr)]}
        busy = xplane.busy_s(tr)
    else:
        metrics = {}
        for m in metrics_for(bench, name, "end_to_end"):
            v = setup_s if m["name"] == "setup_s" else \
                end_to_end(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- correctness: after the window, with the program's state freed --
    # Answers due in the run, its lead-in included: every token served
    # to a request that finished or was still being served at the cut.
    # An open-loop
    # request that got no token by the cut never came; a request that
    # ended otherwise than by its length (error, timeout) failed.
    keep = [i for i, r in enumerate(reqs) if r.finish_reason == "length"
            or (r.finish_reason is None and r.out)]
    done = [(specs[i], reqs[i]) for i in keep]
    if backlog:
        attempted = sum(1 for r in reqs if r.id is not None)
        failed = sum(1 for r in reqs
                     if r.finish_reason not in (None, "length"))
    else:
        attempted = len(reqs)
        failed = attempted - len(done)
    del engine, params, reqs
    session.close()
    gc.collect()
    if not check_outputs:
        return {"metrics": metrics, "notes": notes}
    def compare(cfg: Dict, fp8: bool) -> Dict:
        return check.compare(cfg, ref, key, done, seed,
                             max_len=eng_cfg["max_len"],
                             max_new=max(s.max_new for s in specs),
                             cached=[served[i].cached for i in keep],
                             fp8=fp8)

    verdict = compare(config, control)
    if details is not None:
        details.update(verdict=verdict, compare=compare)
    gap = verdict["control_gap"] if control else verdict["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap,
                                "limit": config["correct"]["max_logit_gap"]},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and verdict["compared_tokens"] > 0
    notes.append(f"compared {verdict['compared_tokens']} served tokens of "
                 f"{verdict['compared_requests']} requests")
    if control:
        notes.append("control: the fp8 reference's first-ranked tokens "
                     "are compared (the program served widest gap "
                     f"{verdict['max_logit_gap']})")
    result.update(correct=bool(correct), attempted=attempted, failed=failed,
                  metrics=metrics,
                  device={"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs),
                          "memory_peak_bytes": int(peak)})
    if trace:
        result["device"].update(busy_s=busy, window_s=run.trace_window_s)
    result["notes"] = notes
    result["checks"] = checks
    return result


def emit(result: Dict) -> None:
    """Print the numbers compared as the last lines of standard error,
    then the result as the last line of standard output."""
    for note in result.get("notes", []):
        print(note, file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
