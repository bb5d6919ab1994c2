"""The served work's share of the chip's bf16 peak for A.X-K1: the
operations that the prompts prefilled and the tokens decoded in the window
need (benchmarks/kernels/axk1_step.py: experts by the assignments the
program counted) over the window's seconds and the peak."""
from benchmarks.kernels import axk1_step


def read(run, trace):
    routing = run.get("routing")
    if not routing:
        return None
    held = sum(r["moe_local_assignments"] for r in routing.values())
    flops = axk1_step.serve_flops(
        run["config"], run["prefilled_prompts"],
        (run["decode_context_sum"], run["decoded_tokens"]), held)
    return 100.0 * flops / (run["window_s"] * run["peak"]["bf16_flops_per_s"])
