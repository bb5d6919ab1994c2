"""The served work's share of the chip's bf16 peak for LFM2-8B-A1B: the
operations that the prompts prefilled and the tokens decoded in the window
need (benchmarks/kernels/lfm2_step.py: experts by the assignments the
program counted) over the window's seconds and the peak."""
from benchmarks import lfm2_read
from benchmarks.kernels import lfm2_step


def read(run, trace):
    cfg = lfm2_read.config_of(run)
    if cfg is None:
        return None
    held = sum(r["moe_local_assignments"] for r in run["routing"].values())
    flops = lfm2_step.serve_flops(
        cfg, run["prefilled_prompts"],
        (run["decode_context_sum"], run["decoded_tokens"]), held)
    return 100.0 * flops / (run["window_s"] * run["peak"]["bf16_flops_per_s"])
