"""Measurement plane: the host seconds the plane spent between the two
readings of the session's counters (``engine.stats()["measurement"]``)
around the traced run, in region entry and exit on the calling threads,
in the background samplers' ticks and in the resolver's passes, over the
time between those readings (their own ``t_s`` clock), in percent.
Moves ``tpot_p90_ms``."""
KEYS = ("region_s", "sampler_s", "resolver_s", "t_s")


def read(run):
    m0 = run.stats0.get("measurement") or {}
    m1 = run.stats1.get("measurement") or {}
    if not all(k in m0 and k in m1 for k in KEYS):
        return None
    elapsed = m1["t_s"] - m0["t_s"]
    if elapsed <= 0:
        return None
    spent = sum(m1[k] - m0[k] for k in KEYS if k != "t_s")
    return 100.0 * spent / elapsed
