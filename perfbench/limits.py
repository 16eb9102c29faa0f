"""Readings that the limit of ``correct`` is set from, in one process.

    python3 perfbench/limits.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 1,2] [--alt layer_norm_eps=1e-5]

For each of ``--seeds``: one run of the cell as the benchmark runs it
(a window of ``--seconds`` at the cell's own load) and its widest logit
gap against the float32 reference: the program's reading.  On the
seeds that are also in ``--control-seeds`` the run is judged as the
control (``harness.run_cell(control=True)``): the tokens the fp8
reference ranks first, on the same prompts and served tokens, take the
program's place, and the run has to read ``correct`` false.  With
``--alt key=value`` the same served tokens are compared once more
against the reference of the configuration with that key changed.

Prints one JSON line per run, then a summary: the largest program
reading (the lower end of the limit) and the smallest control reading
(its upper end), for the configuration as stated and for ``--alt``.
"""
import argparse
import json
import sys
import time

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--alt", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    controls = set(ints(args.control_seeds))
    alt = {}
    if args.alt:
        k, v = args.alt.split("=", 1)
        alt[k] = json.loads(v)
    readings = {"program": [], "control": [], "alt_program": [],
                "alt_control": []}
    for seed in ints(args.seeds):
        d = {}
        ctrl = seed in controls
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             t_start=time.monotonic(), control=ctrl,
                             details=d)
        v = d["verdict"]
        line = {"seed": seed, "control_run": ctrl, "correct": r["correct"],
                "failed": r["failed"], "gap": v["max_logit_gap"],
                "control_gap": v["control_gap"],
                "tokens": v["compared_tokens"]}
        readings["program"].append(v["max_logit_gap"])
        if ctrl:
            readings["control"].append(v["control_gap"])
        if alt:
            cfg = {**d["run"].config, **alt}
            va = d["compare"](cfg, ctrl)
            line.update(alt=alt, alt_gap=va["max_logit_gap"],
                        alt_control_gap=va["control_gap"])
            readings["alt_program"].append(va["max_logit_gap"])
            if ctrl:
                readings["alt_control"].append(va["control_gap"])
        print(json.dumps(line), flush=True)
    pick = lambda f, xs: f(xs) if xs else None
    summary = {"workload": args.workload, "alt": alt,
               "lower": pick(max, readings["program"]),
               "upper": pick(min, readings["control"]),
               "alt_lower": pick(max, readings["alt_program"]),
               "alt_upper": pick(min, readings["alt_control"]),
               "readings": readings}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
