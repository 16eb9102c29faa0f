"""Scheduler: how much of a request's prefill phase the chip spends on
decode steps: over the window's requests that got a first token, the
device time of decode-step programs inside each request's
``serve/req<N>/prefill`` annotation, summed, over those annotations'
summed durations, in percent, averaged over the chips.  Moves
``ttft_p90_ms``."""
import bisect

import xplane


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    want = {f"serve/req{r.rid}/prefill" for r in run.requests
            if r.rid is not None and r.first is not None}
    spans = [(s, s + d) for name, s, d in tr.host if name in want]
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    inside = 0.0
    for mods in tr.modules:
        dec = xplane._union([(o.start_ns, o.start_ns + o.dur_ns)
                             for m in mods if m.kind == "decode"
                             for o in m.ops])
        starts = [s for s, _ in dec]
        for s, e in spans:
            i = max(0, bisect.bisect_right(starts, s) - 1)
            while i < len(dec) and dec[i][0] < e:
                inside += max(0.0, min(e, dec[i][1]) - max(s, dec[i][0]))
                i += 1
    return 100.0 * inside / tr.chips / total
