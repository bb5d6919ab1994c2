"""Share of its roofline that one LFM2 decode step reaches: the least time
to read the weights outside the experts once, each expert that got a row
(`moe_experts_hit` per step x its three matrices), the live K/V rows and
the slots' convolution state, all at the width the loop reads them, or to
do the step's operations (benchmarks/kernels/lfm2_step.py), over
`jit_decode`'s device time per step."""
from benchmarks import axk1_read as r
from benchmarks import lfm2_read
from benchmarks.kernels import lfm2_step as k


def read(run, trace):
    cfg = lfm2_read.config_of(run)
    per = r.routing_per_step(run) if cfg else None
    steps = r.traced_steps(run, trace)
    if per is None or steps is None:
        return None
    least = r.least_ms(
        k.decode_step_bytes(cfg, r.BYTES[cfg["serve"]["weight_dtype"]],
                            r.BYTES[run["engine"]["cache_dtype"]],
                            run["mean_live_tokens"], run["mean_live_slots"],
                            per["moe_experts_hit"]),
        k.decode_step_ops(cfg, run["mean_live_slots"],
                          run["mean_live_tokens"],
                          per["moe_local_assignments"]), run["peak"])
    return 100.0 * least / (steps[0] / steps[1] * 1e3)
