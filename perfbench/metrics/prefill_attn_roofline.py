"""Kernels: the paged prefill-attention kernel's share of its roofline,
reckoned as ``decode_attn_roofline`` from the prompt positions each
chunk prefilled and the keys before them (``flops.prefill_work``).
Moves ``ttft_p90_ms``."""
import flops
import program
import xplane


def read(run):
    if run.trace is None or not run.peaks:
        return None
    kernel = program.KERNELS["prefill_attention"]
    t = xplane.op_time_s(run.trace).get(kernel, 0.0)
    w = [flops.prefill_work(run.family, run.config, r.prompt_len, r.cached,
                            run.chunk)
         for r in run.all_requests if r.first is not None]
    f = sum(x["attn_flops"] for x in w)
    b = sum(x["attn_bytes"] for x in w)
    if t <= 0 or b <= 0:
        return None
    least = max(f / run.peaks["bf16_flops_per_s"],
                b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
