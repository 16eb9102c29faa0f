"""Scheduler: how full the decode batch ran: rows decoding times steps
over steps times the batch's slots, from the engine's ``decode_steps``
and ``decode_row_steps`` counters across the traced run, in percent.
Moves ``out_tok_per_s``."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    if "decode_row_steps" not in s0 or "decode_row_steps" not in s1:
        return None
    steps = s1["decode_steps"] - s0["decode_steps"]
    if steps <= 0:
        return None
    rows = s1["decode_row_steps"] - s0["decode_row_steps"]
    return 100.0 * rows / (steps * s1["batch_slots"])
