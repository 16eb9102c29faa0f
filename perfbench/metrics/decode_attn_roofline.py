"""Kernels: the paged decode-attention kernel's share of its roofline.
The least time is the larger of the operations over the bf16 peak and
the bytes over the HBM bandwidth, both reckoned from the live context
of every decode step (``flops.decode_work``), over the kernel's device
time, in percent.  At one query per row it is bandwidth-bound.  Moves
``tpot_p90_ms``."""
import flops
import program
import xplane


def read(run):
    if run.trace is None or not run.peaks:
        return None
    kernel = program.KERNELS["decode_attention"]
    t = xplane.op_time_s(run.trace).get(kernel, 0.0)
    w = [flops.decode_work(run.family, run.config, r.prompt_len,
                           r.served)
         for r in run.all_requests if r.served]
    f = sum(x["attn_flops"] for x in w)
    b = sum(x["attn_bytes"] for x in w)
    if t <= 0 or b <= 0:
        return None
    least = max(f / run.peaks["bf16_flops_per_s"],
                b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
