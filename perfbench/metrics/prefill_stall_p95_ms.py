"""Scheduler: p95 of the engine's ``stall_events``, the seconds a live
decode batch waited behind each fenced prefill dispatch.  Moves
``tpot_p90_ms``."""
from harness import percentile


def read(run):
    p = percentile(run.stall_events, 95)
    return None if p is None else p * 1e3
