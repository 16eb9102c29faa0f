"""Reduction of a JAX profiler trace to device times.

``reduce(path)`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns, averaged or summed over the TPU chips in it:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``modules``: each execution of a compiled program, with the names of
  the operations inside it, so that a step program is told apart by
  the kernels it runs (a decode step runs the paged decode-attention
  kernel, a prefill chunk the paged prefill-attention kernel);
* ``op_time``: seconds by operation label (a Pallas kernel by its
  name, anything else by its HLO op name without the trailing number);
* ``gaps``: the idle gaps between busy intervals, each with the kinds
  of program before and after it and what the host's threads were in.

``Trace`` is the plain data the readers of per-layer metrics use; the
tests build one by hand.
"""
from __future__ import annotations

import collections
import dataclasses
import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][a-z0-9\-]*)\(")
# Ops that hold other ops (a scanned layer stack runs as a while loop):
# counted in the busy union, left out of time by op.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str                   # label: kernel name or HLO op kind
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Module:
    name: str
    start_ns: float
    dur_ns: float
    ops: List[Op] = dataclasses.field(default_factory=list)
    kind: str = "other"


@dataclasses.dataclass
class Trace:
    chips: int
    ops: List[List[Op]]                 # per chip
    modules: List[List[Module]]         # per chip
    host: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)           # (name, start_ns, dur_ns)


def op_label(name: str, stats: Dict[str, object],
             kernels: Sequence[str]) -> str:
    """A kernel's name where the op or its metadata names one; else a
    fusion's name or the op's opcode, without the trailing number.  On
    a TPU an op event's name is its HLO instruction:
    ``%fusion.117 = bf16[64,8,128]{...} fusion(...), kind=...``."""
    text = " ".join([name] + [str(v) for v in stats.values()
                              if isinstance(v, str)])
    for k in sorted(kernels, key=len, reverse=True):
        if k in text:
            return k
    m = _HLO.match(name)
    if not m:
        return _SUFFIX.sub("", name)
    op, code = _SUFFIX.sub("", m.group(1)), m.group(2)
    return op if code == "fusion" and op != "fusion" else code


def load(path: str, kernels: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, mods, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, e.start_ns, e.duration_ns))
            continue
        if not _DEVICE_PLANE.match(plane.name):
            continue
        chip_ops, chip_mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    chip_ops.append(Op(op_label(e.name, dict(e.stats),
                                                kernels),
                                       e.start_ns, e.duration_ns))
            elif line.name == "XLA Modules":
                for e in line.events:
                    chip_mods.append(Module(e.name, e.start_ns,
                                            e.duration_ns))
        ops.append(chip_ops)
        mods.append(chip_mods)
    return Trace(len(ops), ops, mods, host)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def assign_ops(trace: Trace, kinds: Dict[str, str]) -> None:
    """Put each op into the module execution that holds it, and name each
    module's kind by the first of ``kinds`` (op label -> kind) that it
    runs."""
    for ops, mods in zip(trace.ops, trace.modules):
        mods.sort(key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        for op in ops:
            i = bisect.bisect_right(starts, op.start_ns) - 1
            if i >= 0 and op.start_ns < mods[i].start_ns + mods[i].dur_ns:
                mods[i].ops.append(op)
        for m in mods:
            labels = {o.name for o in m.ops}
            m.kind = next((k for lab, k in kinds.items() if lab in labels),
                          "other")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the chips."""
    if not trace.chips:
        return 0.0
    tot = 0.0
    for ops in trace.ops:
        tot += sum(e - s for s, e in _union(
            [(o.start_ns, o.start_ns + o.dur_ns) for o in ops]))
    return tot / trace.chips * 1e-9


def op_time_s(trace: Trace) -> Dict[str, float]:
    """Device seconds by op label, summed over the chips (ops that hold
    other ops left out)."""
    t: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.ops:
        for o in ops:
            if o.name not in CONTAINERS:
                t[o.name] += o.dur_ns * 1e-9
    return dict(t)


def module_time_s(trace: Trace, kind: str) -> float:
    """Device seconds of the module executions of ``kind``: the union of
    their ops' intervals, so time between a module's ops is not
    counted."""
    return sum(e - s for mods in trace.modules for m in mods
               if m.kind == kind
               for s, e in _union([(o.start_ns, o.start_ns + o.dur_ns)
                                   for o in m.ops])) * 1e-9


def idle_gaps(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps of the first chip, each named by the
    program kinds around it and the host event that covers most of it."""
    if not trace.chips:
        return []
    mods = sorted(trace.modules[0], key=lambda m: m.start_ns)
    busy = _union([(o.start_ns, o.start_ns + o.dur_ns)
                   for o in trace.ops[0]])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for s, e in gaps[:top]:
        inside = next((m.kind for m in mods if m.start_ns <= s
                       and e <= m.start_ns + m.dur_ns), None)
        if inside is not None:
            where = f"in {inside}"
        else:
            before = next((m.kind for m in reversed(mods)
                           if m.start_ns <= s), "start")
            after = next((m.kind for m in mods if m.start_ns >= e), "end")
            where = f"{before}->{after}"
        out.append((f"{where}: {_host_in(trace.host, s, e)}",
                    (e - s) * 1e-9))
    return out


def _host_in(host: List[Tuple[str, float, float]], s: float,
             e: float) -> str:
    """The shortest host event that covers at least half of [s, e)."""
    best: Optional[Tuple[float, str]] = None
    for name, hs, hd in host:
        cover = min(e, hs + hd) - max(s, hs)
        if cover >= 0.5 * (e - s) and (best is None or hd < best[0]):
            best = (hd, name)
    return best[1] if best else "no host event"


def top_ops(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    t = op_time_s(trace)
    return sorted(t.items(), key=lambda kv: kv[1], reverse=True)[:top]
