"""Model step: operations the prefill chunks of the traced run require
(``flops.prefill_work``: prompt positions not served from the prefix
cache, one row of logits each) over the device time of the
prefill-chunk program times the chip's bf16 peak, in percent.  Moves
``ttft_p90_ms``."""
import flops
import xplane


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = xplane.module_time_s(run.trace, "prefill_chunk")
    work = sum(flops.prefill_work(run.family, run.config, r.prompt_len,
                                  r.cached, run.chunk)["flops"]
               for r in run.all_requests if r.first is not None)
    if t <= 0 or work <= 0:
        return None
    return 100.0 * work / (t * run.peaks["bf16_flops_per_s"])
