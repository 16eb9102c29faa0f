"""Scheduler: p90 of the wait from a request's due time to its
admission (the opening of its ``serve/req<N>`` span), over the
requests the engine admitted.  Moves ``ttft_p90_ms``."""
from harness import percentile


def read(run):
    return percentile([(r.admit - r.due) * 1e3 for r in run.requests
                       if r.admit is not None], 90)
