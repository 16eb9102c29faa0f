"""Model step: operations the decode steps of the traced run require
(``flops.decode_work``: live rows at their live lengths) over the
device time of the decode-step program times the chip's bf16 peak, in
percent.  Moves ``tpot_p90_ms``."""
import flops
import xplane


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = xplane.module_time_s(run.trace, "decode")
    work = sum(flops.decode_work(run.family, run.config, r.prompt_len,
                                 r.served)["flops"]
               for r in run.all_requests if r.served)
    if t <= 0 or work <= 0:
        return None
    return 100.0 * work / (t * run.peaks["bf16_flops_per_s"])
