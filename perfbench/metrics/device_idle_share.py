"""Device: the share of the traced window in which no operation ran
on the chip (1 - union of op intervals / window), in percent.  Moves
``tpot_p90_ms``."""
import xplane


def read(run):
    if run.trace is None or not run.trace.chips or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - xplane.busy_s(run.trace) / run.trace_window_s)
