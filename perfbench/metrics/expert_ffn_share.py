"""Model step: the routed experts' share of the chip's busy time.  The
program runs a MoE layer's held experts under one ``lax.cond`` (the
``moe/experts`` scope of ``models/moe.py``), which a device trace shows
as a ``conditional`` op spanning the experts' work; their time inside
the step programs over the busy time of the traced run, in percent.
None where no step program holds such an op.  Moves ``tpot_p90_ms``."""
import xplane

STEP_KINDS = ("decode", "prefill_chunk")


def expert_time_s(trace):
    """Device seconds of the step programs' ``conditional`` ops, summed
    over the chips."""
    return sum(o.dur_ns for mods in trace.modules for m in mods
               if m.kind in STEP_KINDS
               for o in m.ops if o.name == "conditional") * 1e-9


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    t = expert_time_s(run.trace) / run.trace.chips
    busy = xplane.busy_s(run.trace)
    if t <= 0 or busy <= 0:
        return None
    return 100.0 * t / busy
