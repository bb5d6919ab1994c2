"""The whole train step's share of the chip's bf16 peak: operations per
token (benchmarks/kernels/gpt_step.py) times the tokens of the window over
the window's seconds and the peak."""
from benchmarks.kernels import gpt_step


def read(run, trace):
    flops = gpt_step.train_flops_per_token(run["config"], run["seq"]) \
        * run["tokens"]
    return 100.0 * flops / (run["window_s"] * run["peak"]["bf16_flops_per_s"])
