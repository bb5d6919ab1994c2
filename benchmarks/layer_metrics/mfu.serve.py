"""The served work's share of the chip's bf16 peak: the operations that
the prompts prefilled and the tokens decoded in the window need
(benchmarks/kernels/gpt_step.py) over the window's seconds and the peak."""
from benchmarks.kernels import gpt_step


def read(run, trace):
    flops = gpt_step.serve_flops(
        run["config"], run["prefilled_prompts"],
        (run["decode_context_sum"], run["decoded_tokens"]))
    return 100.0 * flops / (run["window_s"] * run["peak"]["bf16_flops_per_s"])
