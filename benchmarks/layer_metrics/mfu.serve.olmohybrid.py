"""The served work's share of the chip's bf16 peak for Olmo-Hybrid-7B: the
operations that the prompts prefilled (the chunked scan's included) and the
tokens decoded in the window need (benchmarks/kernels/olmo_hybrid_step.py)
over the window's seconds and the peak."""
from benchmarks import olmo_hybrid_read
from benchmarks.kernels import olmo_hybrid_step


def read(run, trace):
    cfg = olmo_hybrid_read.config_of(run)
    if cfg is None:
        return None
    flops = olmo_hybrid_step.serve_flops(
        cfg, run["prefilled_prompts"],
        (run["decode_context_sum"], run["decoded_tokens"]))
    return 100.0 * flops / (run["window_s"] * run["peak"]["bf16_flops_per_s"])
