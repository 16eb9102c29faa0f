"""Find the highest rate an open-loop cell sustains, by a sweep.

    python3 perfbench/sweep.py --workload <cell> --seconds <s> \\
        --rates 0.5,0.6,0.7 [--seed <n>]

Runs the cell once per rate in one process (outputs are not compared),
each with the mix's lead-in (``warm_s``) before a window of
``--seconds``, and prints, per rate, the requests due in the window,
how many got a first token, the p50 and p90 time to first token, the
p90 gap between tokens, and the p90 wait from due time to admission
over the window and over each of its halves.  A rate is sustained when
every request due gets its first token and the admission wait neither
exceeds ``MAX_WAIT_S`` nor grows from the first half of the window
to the second by more than that: the queue does not build up.
"""
import argparse
import json
import sys
import time

import harness

MAX_WAIT_S = 1.0    # a few turns of the scheduler's loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in [float(x) for x in args.rates.split(",")]:
        d = {}
        harness.run_cell(args.workload, args.seed, args.seconds, False,
                         t_start=time.monotonic(),
                         mix_update={"rate_per_s": rate}, details=d,
                         check_outputs=False)
        run = d["run"]
        reqs = run.requests
        ttft = [(r.first - r.due) * 1e3 for r in reqs if r.first]
        wait = lambda rs: harness.percentile(
            [(r.admit - r.due) for r in rs if r.admit is not None], 90)
        half = run.t0 + run.window_s / 2
        w_all = wait(reqs)
        w1 = wait([r for r in reqs if r.due < half])
        w2 = wait([r for r in reqs if r.due >= half])
        firsts = sum(r.first is not None for r in reqs)
        sustained = (firsts == len(reqs) and None not in (w_all, w1, w2)
                     and w_all <= MAX_WAIT_S and w2 - w1 <= MAX_WAIT_S)
        print(json.dumps({
            "rate": rate, "requests": len(reqs), "first_tokens": firsts,
            "lead_in_requests": len(run.lead_in),
            "ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p90_ms": harness.end_to_end("ttft_p90_ms", run),
            "tpot_p90_ms": harness.end_to_end("tpot_p90_ms", run),
            "wait_p90_s": w_all, "wait_p90_s_first_half": w1,
            "wait_p90_s_second_half": w2,
            "ended_after_close_s": d["ended_s"] - run.window_s,
            "sustained": sustained}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
