"""Scheduler: the share of the traced window in which the chip sat
idle while the engine's thread was at work: the device's idle gaps
(between busy intervals, as ``xplane.idle_gaps`` finds them) inside
the union of the engine's ``engine/*`` phase annotations other than
``engine/wait``, in percent, averaged over the chips.  Idle time under
``engine/wait`` had nothing to run.  Moves ``tpot_p90_ms``."""
import xplane


def _length(intervals):
    return sum(e - s for s, e in intervals)


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or run.trace_window_s <= 0:
        return None
    host = xplane._union([(s, s + d) for name, s, d in tr.host
                          if name.startswith("engine/")
                          and name != "engine/wait"])
    if not host:
        return None
    idle = 0.0
    for ops in tr.ops:
        busy = xplane._union([(o.start_ns, o.start_ns + o.dur_ns)
                              for o in ops])
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
        # Both lists are unions, so what they share is what their
        # lengths hold beyond the length of their union.
        idle += _length(gaps) + _length(host) \
            - _length(xplane._union(gaps + host))
    return 100.0 * idle / tr.chips * 1e-9 / run.trace_window_s
