"""Model step: the routed experts' share of their roofline.  The least
time is the larger of the operations over the bf16 peak and the bytes
over the HBM bandwidth; the operations are those the assignments to the
held experts require (the engine's ``moe_held_assignments`` across the
traced run, 3 matmuls of hidden x expert width each), the bytes the
held experts' weights read once in every MoE layer of every step
program, and each routed token's hidden row read and written once a
layer.  Over the experts' device time (``expert_ffn_share``), in
percent.  None on a program without the counters.  Moves
``tpot_p90_ms``."""
import pathlib
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "perfbench_metrics_expert_ffn_share",
    pathlib.Path(__file__).with_name("expert_ffn_share.py"))
_share = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_share)

BYTES = 2       # bf16 weights and activations


def read(run):
    s0, s1, c = run.stats0, run.stats1, run.config
    if run.trace is None or not run.peaks \
            or "moe_held_assignments" not in s1:
        return None
    held = sum(s1["moe_held_assignments"]) - sum(
        s0.get("moe_held_assignments", [0]))
    routed = s1["moe_assignments"] - s0.get("moe_assignments", 0)
    t = _share.expert_time_s(run.trace)
    d, ff = c["hidden_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    steps = sum(1 for mods in run.trace.modules for m in mods
                if m.kind in _share.STEP_KINDS)
    if t <= 0 or held <= 0 or steps <= 0:
        return None
    flops = 2.0 * 3 * d * ff * held
    weights = steps * layers * c["n_routed_experts"] * 3 * d * ff * BYTES
    rows = routed / c["num_experts_per_tok"]    # token rows, every layer
    least = max(flops / run.peaks["bf16_flops_per_s"],
                (weights + 2 * rows * d * BYTES)
                / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
